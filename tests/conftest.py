"""Test harness: run everything on a virtual 8-device CPU mesh.

Real TPU hardware is single-chip in CI; sharding correctness is tested
on the CPU backend with 8 virtual devices (SURVEY.md §4 "Distributed").
These env vars must be set before jax initializes its backends.
"""

import os

# Hard-set (not setdefault): whatever the session environment asks
# for, tests run on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# The jaxtyping pytest plugin imports jax before this conftest runs, so
# env vars alone can come too late; the config API works until a backend
# is actually initialized.
jax.config.update("jax_platforms", "cpu")
if len(jax.devices()) < 8:  # pragma: no cover - mis-setup guard
    raise RuntimeError(
        f"test harness expected 8 virtual CPU devices, got {jax.devices()}"
    )

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng() -> np.random.Generator:
    return np.random.default_rng(42)
