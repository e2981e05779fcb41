"""Mesh-sharded store tests on the 8-device virtual CPU mesh.

The reference's analogue is the in-process loopback cluster
(cluster/cluster.go:82-131): N real peers, full peer list known
statically.  Here N shards are N devices in one mesh program.
"""

import random

import jax
import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.parallel.mesh import MeshBucketStore, make_mesh, shard_of_key
from gubernator_tpu.types import Algorithm, RateLimitRequest, Status
from gubernator_tpu.utils.clock import Clock

from . import oracle

T0 = 1_573_430_430_000


def mk(key, hits=1, limit=10, duration=5000, algo=Algorithm.TOKEN_BUCKET):
    return RateLimitRequest(
        name="mesh", unique_key=key, hits=hits, limit=limit, duration=duration, algorithm=algo
    )


def test_requires_8_devices():
    assert len(jax.devices()) == 8


def test_state_is_sharded():
    store = MeshBucketStore(capacity_per_shard=64)
    assert store.n_shards == 8
    shard_dim = store.state.hot.shape[0]
    assert shard_dim == 8
    # each row table must actually be laid out across all 8 devices
    assert len(store.state.hot.sharding.device_set) == 8


def test_shard_assignment_is_stable_and_covers():
    n = 8
    seen = set()
    for i in range(2000):
        s = shard_of_key(f"name_k{i}", n)
        assert 0 <= s < n
        seen.add(s)
    assert seen == set(range(n))  # all shards get traffic


def test_mesh_matches_single_shard_semantics():
    """The sharded store must give the sequential reference's answers
    (tests/oracle.py: one cache, one request at a time) on the same
    workload."""
    rng = random.Random(7)
    mesh_store = MeshBucketStore(capacity_per_shard=256)
    ref = oracle.OracleCache()
    clock = Clock()
    clock.freeze(T0)
    for _ in range(30):
        batch = []
        for _ in range(rng.randrange(1, 40)):
            batch.append(
                mk(
                    key=f"k{rng.randrange(64)}",
                    hits=rng.choice([0, 1, 2, 5]),
                    limit=rng.choice([5, 100]),
                    duration=rng.choice([1000, 60_000]),
                    algo=rng.choice([Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]),
                )
            )
        now = clock.now_ms()
        got = mesh_store.apply(batch, now)
        want = [oracle.apply(ref, req, now) for req in batch]
        for g, w, req in zip(got, want, batch):
            assert (g.status, g.limit, g.remaining, g.reset_time) == (
                w.status, w.limit, w.remaining, w.reset_time,
            ), req
        clock.advance(rng.choice([0, 10, 900, 5000]))


def test_mesh_duplicate_keys_serialize():
    store = MeshBucketStore(capacity_per_shard=64)
    reqs = [mk("dup", hits=3, limit=10) for _ in range(4)]
    resps = store.apply(reqs, T0)
    assert [r.remaining for r in resps] == [7, 4, 1, 1]
    assert resps[3].status == Status.OVER_LIMIT


def test_mesh_scales_keyspace():
    """1k distinct keys land across shards and all get correct answers."""
    store = MeshBucketStore(capacity_per_shard=512)
    reqs = [mk(f"k{i}", hits=1, limit=7) for i in range(1000)]
    resps = store.apply(reqs, T0)
    assert all(r.remaining == 6 for r in resps)
    assert store.size() == 1000
    per_shard = [len(t) for t in store.tables]
    assert min(per_shard) > 0


@pytest.mark.parametrize(
    "fused_native",
    [
        pytest.param(
            True,
            marks=pytest.mark.skipif(
                not native.available(),
                reason="native runtime unavailable: True case would be Python-vs-Python",
            ),
        ),
        False,
    ],
)
def test_fused_duplicates_match_sequential(fused_native):
    """Hot-key duplicate batches through the fused mesh dispatch
    (grouped round 0 + slow rounds in one program) must match applying
    the same requests one at a time — with the fused store on BOTH slot
    table backends, pinning C++/Python table parity through the mesh
    path (the serial store always runs the Python tables)."""
    import numpy as np

    from gubernator_tpu.parallel.mesh import MeshBucketStore
    from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest

    rng = np.random.RandomState(9)
    fused = MeshBucketStore(capacity_per_shard=128, g_capacity=32,
                            use_native=fused_native)
    serial = MeshBucketStore(capacity_per_shard=128, g_capacity=32,
                             use_native=False)
    now = 1_700_000_000_000
    for step in range(25):
        reqs = []
        # uniform hot group
        for _ in range(rng.randint(1, 12)):
            reqs.append(RateLimitRequest(
                name="mf", unique_key="hot", hits=1, limit=9, duration=4_000,
                algorithm=Algorithm.TOKEN_BUCKET,
            ))
        # non-uniform duplicates (slow path)
        for _ in range(rng.randint(0, 6)):
            reqs.append(RateLimitRequest(
                name="mf", unique_key="mix", hits=int(rng.choice([1, 2])),
                limit=7, duration=4_000, algorithm=Algorithm.LEAKY_BUCKET,
            ))
        # occasional RESET_REMAINING (excluded from grouping)
        if rng.random() < 0.3:
            reqs.append(RateLimitRequest(
                name="mf", unique_key="hot", hits=1, limit=9, duration=4_000,
                behavior=Behavior.RESET_REMAINING,
            ))
        rng.shuffle(reqs)
        now += rng.randint(0, 900)
        got = fused.apply(reqs, now)
        want = [serial.apply([r], now)[0] for r in reqs]
        for i, (g, w) in enumerate(zip(got, want)):
            assert (g.status, g.remaining, g.reset_time) == (
                w.status, w.remaining, w.reset_time,
            ), (step, i, reqs[i], g, w)
