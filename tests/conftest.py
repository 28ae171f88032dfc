"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see the real
single CPU device (only launch/dryrun forces 512 placeholder devices).

The persistent compilation cache stays off in tests, including in the
launcher subprocesses some tests start (they inherit the environment):
``launch.compile_cache.enable_compile_cache`` honours it."""
import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)
