import os

# Smoke tests and benches must see the single real CPU device — the 512-device
# override belongs ONLY to repro.launch.dryrun (its own process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses

import jax
import pytest
# re-exported: test modules import these via `from conftest import ...`
from hypothesis import given, settings, strategies as st  # noqa: F401

from repro.configs import get_config


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def f32_reduced(arch: str):
    """Reduced config in float32 (tight numeric comparisons)."""
    return dataclasses.replace(get_config(arch).reduced(), dtype="float32")
