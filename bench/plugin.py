"""Load one of the benchmark's files by its path: a traffic generator
(``traffic/<name>.py``), a predicate shape (``shapes/<name>.py``) or a
per-layer metric's reader (``metrics/<name>.py``). Each is found by the
name a data file gives it, so a later cell adds files and never edits
one."""
from __future__ import annotations

import importlib.util
from pathlib import Path


def load(path: Path):
    """The module in ``path``, loaded under a name of its own."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(f"no benchmark file {path}")
    name = f"bench_{path.parent.name}_{path.stem.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
