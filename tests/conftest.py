"""Test environment: 8 virtual CPU devices so multi-chip sharding semantics
are testable single-process (SURVEY.md §4 'Lesson' item 4).

Tests run on the CPU: an accelerator belongs to one process at a time, and
the on-chip proof is ``chip_smoke.py`` through the chip tool, not pytest.
What the TPU *compiler* accepts is still covered here — libtpu's
compile-only client (tests/test_tpu_compile.py) needs no chip.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Single source of truth for platform forcing + virtual device count: the
# same helper the driver's dryrun uses (__graft_entry__._provision_cpu_mesh).
from __graft_entry__ import _provision_cpu_mesh  # noqa: E402

_provision_cpu_mesh(8)

import jax  # noqa: E402  (import after env vars so they take effect)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); covered by "
        "the smoke scripts under tools/")


@pytest.fixture
def rng():
    return np.random.RandomState(12345)


@pytest.fixture
def key():
    return jax.random.PRNGKey(12345)
