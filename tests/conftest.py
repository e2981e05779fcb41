"""Test harness configuration.

Forces JAX onto an 8-device virtual CPU mesh so multi-shard sharding
paths run without real multi-chip hardware (the reference's analogue is
the in-process loopback cluster, cluster/cluster.go:82-131).

Tests always run on the CPU: `jax.config.update('jax_platforms', 'cpu')`
below holds whatever JAX_PLATFORMS says.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture
def frozen_clock():
    from gubernator_tpu.utils.clock import Clock

    c = Clock()
    c.freeze(1_573_430_400_000)  # 2019-11-11T00:00:00Z
    return c
