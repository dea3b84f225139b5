"""Test harness config.

Tests run on a virtual 8-device CPU mesh so every multi-chip sharding path
(pjit/shard_map over Mesh) is exercised without TPU hardware — the JAX analog
of the reference's "partitions-as-workers" local-mode trick (SURVEY.md §4:
LightGBM tests make each Spark partition a network worker on localhost).

Env must be set before jax import, hence module scope here.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# Tests always run on the CPU backend, whatever the machine holds.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax

# Importing the package places the persistent compile cache (see
# parallel.distributed.configure_compile_cache): the suite's cost is
# dominated by recompiling the same few hundred CPU programs every run.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import mmlspark_tpu  # noqa: E402,F401

assert jax.default_backend() == "cpu", jax.default_backend()
assert len(jax.devices()) == 8, jax.devices()

import numpy as np
import pytest


def pytest_collection_modifyitems(config, items):
    """Test tiering (reference: TestBase.scala:23-39 Extended/BuildServer tags
    selected by TESTS= env, default -extended). The default tier must finish
    in CI minutes on one core; MMLTPU_TESTS=extended (or =all) runs
    everything — example scripts, multi-process workers, big-model parity."""
    tiers = {t.strip() for t in
             os.environ.get("MMLTPU_TESTS", "").lower().split(",") if t.strip()}
    if tiers & {"extended", "all"}:
        return
    skip = pytest.mark.skip(
        reason="extended tier (set MMLTPU_TESTS=extended to run)")
    for item in items:
        if "extended" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def toy_df():
    from mmlspark_tpu import DataFrame
    rng = np.random.default_rng(0)
    n = 64
    return DataFrame({
        "x1": rng.normal(size=n),
        "x2": rng.normal(size=n),
        "cat": np.array(list("abcd") * (n // 4), dtype=object),
        "label": (rng.random(n) > 0.5).astype(np.float64),
        "text": np.array(["hello world foo", "bar baz qux quux"] * (n // 2),
                         dtype=object),
    })
