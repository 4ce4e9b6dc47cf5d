"""Shared fixtures. Tests run on the single host CPU device (never set
xla_force_host_platform_device_count here — the dry-run owns that knob).

The default artifact store is pointed at a fresh temp dir so test runs
never read a developer's ~/.cache entries (which would turn compile-count
assertions stale) and never pollute it.  Store tests that exercise
cross-process persistence manage their own dirs via ``REPRO_ARTIFACT_DIR``.
"""

import dataclasses
import os
import tempfile

os.environ["REPRO_ARTIFACT_DIR"] = tempfile.mkdtemp(prefix="repro-artifacts-")
# the suite runs on the CPU (interpret-mode kernels) and never holds a chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

import repro.configs as configs
from repro.configs.base import ShapeConfig


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


SMOKE_SHAPE = ShapeConfig("smoke", 32, 2, "train")


@pytest.fixture(scope="session")
def smoke_shape():
    return SMOKE_SHAPE


def dropless(cfg):
    """Copy of a smoke config with MoE capacity high enough to never drop
    tokens — needed when comparing full-sequence vs per-token routing."""
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0)
    )


ALL_ARCHS = configs.ALL_ARCHS
ASSIGNED_ARCHS = configs.ASSIGNED_ARCHS
