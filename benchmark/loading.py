"""Finding the benchmark's files by name: a module from its path, a JSON file."""

from __future__ import annotations

import importlib.util
import json
import sys


def load_module(path: str, name: str):
    """The Python file at ``path`` as a module called ``name`` (configs, deployments, readers)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as f:
        return json.load(f)
