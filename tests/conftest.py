import os

# Smoke tests and benches must see the single real CPU device — the 512-device
# override belongs ONLY to repro.launch.dryrun (its own process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses

import jax
import numpy as np
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


def fingerprint(result, events):
    """Golden summary of one engine run: ``(events, arbitration,
    tenants)``, each tenant ``(name, done, shed, failed, sum of
    finish_ms, p99 sojourn, network bytes, adaptation-log length, batch
    histogram)``. ``result`` is a ``MultiTenantReport`` or a
    ``RunReport`` (its tenant named ``""``). Floats keep 9 significant
    digits, so a last libm bit on another machine cannot fail a golden
    comparison."""
    reports = getattr(result, "reports", None)
    if reports is None:
        reports = {"": result}

    def g9(x):
        return float(f"{float(x):.9g}")

    tenants = []
    for name in sorted(reports):
        rep = reports[name]
        c = rep.columns
        tenants.append((
            name, rep.done_count, rep.shed_count, rep.failed_count,
            g9(c.finish_ms.sum()), g9(np.percentile(c.sojourn_ms, 99)),
            g9(rep.network_bytes),
            None if rep.adaptation is None else len(rep.adaptation["events"]),
            None if rep.batch_hist is None
            else tuple(sorted(rep.batch_hist.items()))))
    arb = getattr(result, "arbitration", None)
    if arb is not None:
        arb = (arb["applied"], arb["deferred"])
    return (events, arb, tuple(tenants))
