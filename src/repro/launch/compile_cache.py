"""Where compiled programs are kept between runs.

Two caches share one root: JAX's persistent compilation cache (XLA
programs, keyed by JAX) and the engine's serialized-executable cache
(``repro.serving.engine``'s AOTRecipe payloads, in ``<root>/pcm-aot``).
Entry points call :func:`configure_compile_cache` before their first
compile. A cache keys on its own path, so the root never moves: it is
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself and nothing here sets another path), else ``.compile_cache`` at
the root of the checkout.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
AOT_SUBDIR = "pcm-aot"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".compile_cache")


def configure_compile_cache() -> str:
    """Point both caches at one fixed root; return the root."""
    from repro.serving.engine import set_aot_cache_dir
    root = os.environ.get(ENV)
    if not root:
        root = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", root)
    set_aot_cache_dir(os.path.join(root, AOT_SUBDIR))
    return root
