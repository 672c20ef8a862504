"""Files the harness finds by a name in a data file: `<root>/<folder>/<name>.py`.

A per-layer reader (`layer_metrics/`), a configuration's reference
(`references/`) and its cache driver (`drivers/`) are each a file of their
own, so that a later PR brings them as new files and edits none.  A data root
(`--root`, e.g. a tiny twin under `benchmarks/tests/`) is searched first; the
benchmark's own folder serves any root that has no file of that name.
"""

from __future__ import annotations

import importlib.util
import os
import re
from typing import Iterable

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def load(roots: Iterable[str], folder: str, name: str):
    """The module at the first `<root>/<folder>/<name>.py` that exists.
    No file is an error, never a default."""
    if not NAME.match(name):
        raise ValueError(f"{folder}: {name!r} is not a name")
    tried = [os.path.join(r, folder, name + ".py") for r in roots]
    path = next((t for t in tried if os.path.exists(t)), None)
    if path is None:
        raise FileNotFoundError(
            f"no {folder}/{name}.py (looked for {', '.join(tried)})")
    spec = importlib.util.spec_from_file_location(
        f"{folder}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
