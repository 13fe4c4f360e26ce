"""Load a module of the benchmark from its file, by the name that a
``BENCHMARK.json`` entry or a configuration file gives it."""

from __future__ import annotations

import importlib.util
import os
from types import ModuleType


def load_module(path: str, name: str) -> ModuleType:
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path} not found for {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
