import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop compiled executables between test modules.

    The suite compiles hundreds of distinct programs in one process;
    clearing per module keeps the process-wide executable cache (and the
    worker's memory) bounded. Nothing in the suite relies on cross-module
    executable reuse -- no-retrace tests warm and assert within a single
    module -- so this changes no test's semantics.
    """
    yield
    import jax

    jax.clear_caches()
