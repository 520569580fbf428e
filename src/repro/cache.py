"""The persistent XLA compilation cache: one rule for where it lives.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and
this module sets nothing.  Otherwise the cache goes to the fixed path
``<checkout>/.cache/jax`` (ignored by git), so that a later run of the
same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """Where the compilation cache lives under the rule above."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".cache", "jax")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process (the
    serving CLI and the chip smoke check call this first) and return its
    directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        import jax
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
