"""Shared tier-1 fixtures and session-level speedups.

- Persistent XLA compilation cache under the package's one rule
  (repro/cache.py): ``JAX_COMPILATION_CACHE_DIR`` where set, else
  ``<checkout>/.cache/jax``.  Exported as env vars before jax is imported,
  so the subprocess tests in test_perf_variants inherit it.
- Session-scoped compiled designs shared across test modules, so the
  full-size CONVOLUTION pipeline and the four small app cases are each
  compiled once.
"""
import os
from fractions import Fraction

import pytest

from repro.cache import ENV, compile_cache_dir

os.makedirs(compile_cache_dir(), exist_ok=True)
os.environ[ENV] = compile_cache_dir()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")


@pytest.fixture(scope="session")
def conv_design_t1():
    """Full-size CONVOLUTION compiled at T=1 (shared by the system tests)."""
    from repro.apps import Convolution
    from repro.core import compile_pipeline
    return compile_pipeline(Convolution(), T=Fraction(1))


@pytest.fixture(scope="session")
def lowering_cases():
    """{app: (compiled HWDesign, inputs_fn)} for the paper's four apps at
    small sizes — the shared substrate of the cross-backend suite."""
    from repro.apps import BENCH_CASES
    from repro.core import compile_pipeline
    cases = {}
    for name, case in BENCH_CASES.items():
        uf, inputs_fn = case()
        cases[name] = (compile_pipeline(uf), inputs_fn)
    return cases
