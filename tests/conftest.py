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


class GroupByTest:
    """The upstream gate job at test size (``GroupByTest <m> 60 25000 200``: int
    keys, 25,000-byte values, ``key mod 200``)."""

    @staticmethod
    def records(mappers: int, seed: int = 29):
        """The job's map output with the plain GroupBy it is checked against —
        ``benchmark/references/groupby.py``, which imports nothing of the package."""
        from benchmark.references import groupby

        config = {"mappers": mappers, "pairs_per_mapper": 60, "value_bytes": 25000,
                  "reducers": 200, "keys": "uniform-int31"}
        return groupby.make_records(config, seed)

    @staticmethod
    def write_and_exchange(mgr, shuffle_id: int, records) -> None:
        """The job's map side through the manager's writers, then the exchange."""
        mgr.register_shuffle(shuffle_id, records.num_mappers, records.reducers)
        for m, parts in enumerate(records.blocks):
            writer = mgr.get_writer(shuffle_id, m)
            for r, payload in parts:
                with writer.get_partition_writer(r).open_stream() as stream:
                    stream.write(payload)
            writer.commit_all_partitions()
        mgr.run_exchange(shuffle_id)


@pytest.fixture(scope="session")
def groupbytest():
    return GroupByTest
