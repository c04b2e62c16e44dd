"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-executor sharding/collectives are
exercised without TPU hardware (the env vars must be set before jax imports).  This
is the unit-test scaffolding the reference never had (SURVEY.md section 4: "There are
no unit tests"); the loopback transport plays the role its ShuffleTransport trait was
designed for ("standalone testing purpose", ShuffleTransport.scala:124-128).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
