"""The architecture a configuration file names, found by that name.

A configuration file's ``"architecture"`` names a module
``bench/architectures/<architecture>.py`` of the checkout the run is made
from, as a traffic mix names its loop and a per-layer metric its reader.
The module is everything the benchmark knows of that architecture:

  check(c)                  refuse a file whose keys this architecture
                            cannot run (raises ValueError)
  program_config(c)         the program's ``ModelConfig`` for the file,
                            refused where the program's family differs
  layout(c)                 the weight leaves: path -> (per-layer shape,
                            fan_in), as ``bench.weights`` fills them
  logits_at(c, seed, tokens, lengths, rows, precision)
                            the plain float32 reference
                            (``precision="reference"``) and its control
                            (``precision="control"``), as
                            ``bench.reference.logits_at`` gives them
  weight_bytes(c), kv_bytes_per_token(c)
  prefill_cost(c, rows, calls), decode_cost(c, rows, steps)
                            the work counts of ``bench.counts``

A new architecture is a new module here and nothing else.
"""

from __future__ import annotations

import os
from types import ModuleType
from typing import Dict, List, Tuple

from bench.modules import load_module

PROVIDES = ("check", "program_config", "layout", "logits_at",
            "weight_bytes", "kv_bytes_per_token", "prefill_cost",
            "decode_cost")

_loaded: Dict[Tuple[str, str], ModuleType] = {}


def directory(root: str) -> str:
    return os.path.join(root, "bench", "architectures")


def names(root: str) -> List[str]:
    """The architectures the checkout at ``root`` has a module for."""
    return sorted(f[:-3] for f in os.listdir(directory(root))
                  if f.endswith(".py") and f != "__init__.py")


def of(c: Dict) -> ModuleType:
    """The module of ``c["architecture"]`` in the checkout ``c["root"]``,
    loaded once per checkout. A name with no module, or a module that
    lacks part of ``PROVIDES``, is refused."""
    name, root = c.get("architecture"), c["root"]
    if (root, name) in _loaded:
        return _loaded[root, name]
    known = names(root)
    if name not in known:
        raise ValueError(f"{c['file']}: no module for architecture "
                         f"{name!r} in {directory(root)}; the modules "
                         f"there are: {', '.join(known) or 'none'}")
    mod = load_module(os.path.join(directory(root), f"{name}.py"), name)
    missing = [f for f in PROVIDES if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"architecture {name!r} provides no "
                         f"{', '.join(missing)}")
    _loaded[root, name] = mod
    return mod
