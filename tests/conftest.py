"""Test harness configuration.

Forces JAX onto an 8-device virtual CPU mesh so multi-shard sharding
paths run without real multi-chip hardware (the reference's analogue is
the in-process loopback cluster, cluster/cluster.go:82-131).

Tests always run on the CPU: `jax.config.update('jax_platforms', 'cpu')`
below holds whatever JAX_PLATFORMS says.
"""

import functools
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


def _store_over(n_devices, capacity, **kw):
    from gubernator_tpu.parallel.mesh import MeshBucketStore

    return MeshBucketStore(
        capacity_per_shard=capacity, devices=jax.devices()[:n_devices], **kw
    )


def one_device_store(capacity, **kw):
    """The daemon's store over ONE device: the shape of `v5e1-1m`, and
    the store for tests that count on one table (the default
    `MeshBucketStore()` is 8 shards under this harness)."""
    return _store_over(1, capacity, **kw)


def take_moves(table):
    """Drain a native table's queued tier moves as the store does
    (`take_moves_into`, one block), split into its five columns: promo
    kind, promo src, promo dst, demo src, demo dst."""
    import numpy as np

    n_promo, n_demo = table.move_counts()
    block = np.empty((5, max(n_promo, n_demo, 1)), dtype=np.int32)
    assert table.take_moves_into(block) == (n_promo, n_demo)
    return (*block[:3, :n_promo], *block[3:, :n_demo])


@pytest.fixture(params=[1, 4], ids=["one-device", "four-shard"])
def make_store(request):
    """`make_store(capacity, **kw)`: the daemon's store in the two
    shapes the benchmark serves, `v5e1-1m` (one device) and
    `v5e4-mesh-1m` (four shards).  Tests that count on ONE table's
    eviction order take `one_device_store` instead."""
    return functools.partial(_store_over, request.param)
