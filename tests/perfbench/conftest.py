"""A Float64 reference (or program) turns on JAX's 64-bit mode for the
whole process; each test here leaves the mode as it found it."""

import jax
import pytest


@pytest.fixture(autouse=True)
def _x64_restored():
    before = jax.config.jax_enable_x64
    yield
    jax.config.update("jax_enable_x64", before)
