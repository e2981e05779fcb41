"""GLOBAL behavior: replica caches, device-side hit accumulation, and
the collective sync program, on the 8-device mesh.

Reference model under test: non-owner answers locally and forwards hits
async (gubernator.go:231-255, global.go:77-160); owner applies and
broadcasts authoritative status (global.go:163-243); peers then answer
from the broadcast cache until it expires (gubernator.go:241-249,
259-272).  Convergence observed here by stepping `sync_globals()` —
the in-process equivalent of waiting out GlobalSyncWait ticks as
TestGlobalRateLimits does by polling metrics (functional_test.go:478-546).
"""

import random
import sys
import threading
import time

import pytest

from gubernator_tpu.parallel.mesh import MeshBucketStore, shard_of_key
from gubernator_tpu.types import (
    Algorithm, Behavior, RateLimitRequest, RateLimitResponse, Status, UpdatePeerGlobal,
)
from gubernator_tpu.utils.clock import Clock

T0 = 1_573_430_430_000
GLOBAL = Behavior.GLOBAL


def mk(key, hits=1, limit=10, duration=60_000, behavior=GLOBAL):
    return RateLimitRequest(
        name="glob", unique_key=key, hits=hits, limit=limit,
        duration=duration, algorithm=Algorithm.TOKEN_BUCKET, behavior=behavior,
    )


def owner_and_other(store, key):
    owner = shard_of_key(f"glob_{key}", store.n_shards)
    other = (owner + 1) % store.n_shards
    return owner, other


def test_non_owner_answers_locally_then_converges():
    store = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    owner, other = owner_and_other(store, "k1")

    # First hit lands at a non-owner: replica cache is cold, so it
    # computes as-if-owner locally (gubernator.go:250-254).
    r = store.apply([mk("k1")], T0, home_shard=other)[0]
    assert r.status == Status.UNDER_LIMIT and r.remaining == 9

    # Sync: the hit reaches the owner, owner broadcasts.
    res = store.sync_globals(T0 + 1)
    assert res.broadcast_count == 1
    assert store.gtable.rep_expire[store.gtable.get("glob_k1")] > T0

    # Now the non-owner answers from the broadcast cache: remaining is
    # the owner's authoritative value, static until the next broadcast.
    r = store.apply([mk("k1")], T0 + 2, home_shard=other)[0]
    assert r.status == Status.UNDER_LIMIT and r.remaining == 9
    r = store.apply([mk("k1")], T0 + 3, home_shard=other)[0]
    assert r.remaining == 9  # still the cached value (reference semantics)

    # Those two cached hits converge at the next sync.
    store.sync_globals(T0 + 4)
    g = store.gtable.get("glob_k1")
    assert store.gtable.rep_expire[g] > T0
    r = store.apply([mk("k1", hits=0)], T0 + 5, home_shard=other)[0]
    assert r.remaining == 7  # 10 - 1 (pre-sync) - 2 (cached hits)


def test_owner_local_hits_broadcast_without_forwarding():
    store = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    owner, other = owner_and_other(store, "k2")

    # Hits at the owner apply directly (gubernator.go:176) and mark the
    # key dirty for broadcast (QueueUpdate, gubernator.go:339-341).
    r = store.apply([mk("k2", hits=4)], T0, home_shard=owner)[0]
    assert r.remaining == 6
    store.sync_globals(T0 + 1)

    # Another shard answers from the broadcast without ever computing.
    r = store.apply([mk("k2", hits=1)], T0 + 2, home_shard=other)[0]
    assert r.remaining == 6  # owner's broadcast value


def test_hot_key_skew_converges_across_shards():
    """BASELINE config 4: GLOBAL hot key hammered from every shard."""
    store = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    owner, _ = owner_and_other(store, "hot")
    limit = 1000
    total = 0
    clock = Clock()
    clock.freeze(T0)

    # Warm the cache with one owner-side hit + sync.
    store.apply([mk("hot", hits=1, limit=limit)], clock.now_ms(), home_shard=owner)
    total += 1
    store.sync_globals(clock.now_ms())

    # 5 windows of skewed traffic from every shard.
    for window in range(5):
        clock.advance(10)
        for s in range(store.n_shards):
            if s == owner:
                continue
            hits = 7 + (s % 3)
            r = store.apply(
                [mk("hot", hits=hits, limit=limit)], clock.now_ms(), home_shard=s
            )[0]
            assert r.status == Status.UNDER_LIMIT  # cached answers
            total += hits
        clock.advance(10)
        store.sync_globals(clock.now_ms())

    # The authoritative count must equal the exact sum of all hits.
    r = store.apply([mk("hot", hits=0, limit=limit)], clock.now_ms(), home_shard=owner)[0]
    assert r.remaining == limit - total


def test_over_limit_propagates_to_replicas():
    store = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    owner, other = owner_and_other(store, "k3")

    store.apply([mk("k3", hits=10, limit=10)], T0, home_shard=owner)
    store.sync_globals(T0 + 1)

    # The broadcast carries the owner's STICKY status: draining to 0 via
    # a hits==limit create leaves Status UNDER_LIMIT (algorithms.go:
    # 147-159 never sets it), so replicas serve UNDER/0 until a hit
    # actually bounces at the owner.
    for i in range(3):
        r = store.apply([mk("k3", hits=1, limit=10)], T0 + 2 + i, home_shard=other)[0]
        assert r.status == Status.UNDER_LIMIT
        assert r.remaining == 0

    # Next sync: the 3 forwarded hits bounce (remaining==0 & hits>0 =>
    # OVER + sticky, algorithms.go:112-117) and OVER propagates.
    store.sync_globals(T0 + 9)
    r = store.apply([mk("k3", hits=0, limit=10)], T0 + 10, home_shard=owner)[0]
    assert r.status == Status.OVER_LIMIT
    assert r.remaining == 0
    r = store.apply([mk("k3", hits=1, limit=10)], T0 + 11, home_shard=other)[0]
    assert r.status == Status.OVER_LIMIT  # replica now serves OVER from cache


def test_gslot_eviction_clears_device_rows():
    """A recycled gslot must never serve the evicted key's broadcast."""
    store = MeshBucketStore(capacity_per_shard=64, g_capacity=2)

    # Warm e1: broadcast makes its replica rows live (remaining=4).
    owner1, other1 = owner_and_other(store, "e1")
    store.apply([mk("e1", hits=6, limit=10)], T0, home_shard=owner1)
    store.sync_globals(T0 + 1)
    g_e1 = store.gtable.get("glob_e1")
    assert store.gtable.rep_expire[g_e1] > T0

    # Two more keys exhaust the 2-entry table; e1 is evicted and its
    # gslot recycled for e3.
    for k in ["e2", "e3"]:
        _, oth = owner_and_other(store, k)
        store.apply([mk(k)], T0 + 2, home_shard=oth)
    assert store.gtable.get("glob_e1") is None
    g_e3 = store.gtable.get("glob_e3")
    assert g_e3 == g_e1  # recycled

    # e3's non-owner answer above must have computed locally (fresh
    # bucket: 10-1=9), not served e1's stale broadcast (remaining=4).
    _, oth3 = owner_and_other(store, "e3")
    r = store.apply([mk("e3", hits=0)], T0 + 3, home_shard=oth3)[0]
    assert r.remaining == 9


def test_autotune_sizes_the_window_from_sync_cost():
    """The GlobalManager sizes the sync window from its in-situ sync
    timings (<=10% overhead, clamped) once GLOBAL traffic is observed."""
    from gubernator_tpu.service import GlobalManager, ServiceConfig, V1Service
    from gubernator_tpu.types import PeerInfo

    store = MeshBucketStore(capacity_per_shard=256, g_capacity=64)

    clock = Clock()
    clock.freeze(T0)
    svc = V1Service(ServiceConfig(store=store, clock=clock,
                                  advertise_address="127.0.0.1:9991"))
    svc.set_peers([PeerInfo(grpc_address="127.0.0.1:9991", is_owner=True)])
    try:
        mgr = svc.global_mgr
        # default config leaves the window on AUTO at the fallback value
        assert mgr._auto and mgr.sync_wait_s == GlobalManager.SYNC_WAIT_FALLBACK_S
        # drive ticks manually: the background interval must not race us
        mgr._interval.stop()
        from gubernator_tpu.types import GetRateLimitsRequest

        svc.get_rate_limits(
            GetRateLimitsRequest(requests=[mk("tune", hits=1, limit=10)])
        )
        mgr._tick()  # one real tick: does work, observes its own cost
        assert mgr.measured_sync_cost_s is not None
        expected = GlobalManager.window_for_cost(mgr.measured_sync_cost_s)
        assert mgr.sync_wait_s == pytest.approx(expected)
        assert mgr._interval.duration_s == pytest.approx(expected)
        # still AUTO: the window keeps adapting as sync cost changes
        assert mgr._auto
        # The estimator is min-of-recent (best-of-N): ONE contaminated
        # outlier must NOT move the window (round 4: a single ~300ms
        # startup sample had locked the EMA at the 1s clamp)...
        before = mgr.sync_wait_s
        mgr._observe_sync_cost(10.0)
        assert mgr.sync_wait_s == pytest.approx(before)
        # ...but a SUSTAINED cost rise lifts every sample in the deque
        # and the window follows, clamped at the max.
        for _ in range(GlobalManager.SYNC_COST_SAMPLES):
            mgr._observe_sync_cost(10.0)
        assert mgr.sync_wait_s == GlobalManager.SYNC_WAIT_MAX_S
    finally:
        svc.close()


def test_configured_sync_wait_disables_autotune():
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.service import ServiceConfig, V1Service
    from gubernator_tpu.types import PeerInfo

    clock = Clock()
    clock.freeze(T0)
    svc = V1Service(ServiceConfig(
        cache_size=256,
        behaviors=BehaviorConfig(global_sync_wait_s=0.05),
        clock=clock, advertise_address="127.0.0.1:9992",
    ))
    svc.set_peers([PeerInfo(grpc_address="127.0.0.1:9992", is_owner=True)])
    try:
        assert not svc.global_mgr._auto
        assert svc.global_mgr.sync_wait_s == 0.05
    finally:
        svc.close()


def test_global_cache_auto_sizes_to_bucket_capacity():
    """Unset global_cache_size auto-sizes the replica table to the
    bucket-table capacity, clamped [4096, 65536] — the reference has no
    separate GLOBAL key cap (GLOBAL keys share its cache,
    global.go:83-91), so a working set that fits the cache must fit the
    replica table.  An explicit setting still wins."""
    from gubernator_tpu.service import ServiceConfig, V1Service

    for cache, explicit, want in (
        (256, None, 4096),        # clamp floor
        (20_000, None, 20_000),   # match capacity
        (500_000, None, 65_536),  # clamp ceiling
        (20_000, 512, 512),       # explicit wins
    ):
        svc = V1Service(ServiceConfig(
            cache_size=cache, global_cache_size=explicit,
        ))
        try:
            assert svc.store.g_capacity == want, (cache, explicit, want)
        finally:
            svc.close()


def test_sync_fast_path_survives_owner_slot_eviction():
    """The generation-gated resolution fast path (round 5): a sync pass
    skips owner-slot verification for shards with no mapping churn, but
    MUST re-resolve when the owner's slot was evicted between syncs —
    the stale slot would otherwise read another key's row."""
    store = MeshBucketStore(capacity_per_shard=4, g_capacity=32)
    owner, _ = owner_and_other(store, "gk")

    store.apply([mk("gk", hits=3, limit=10)], T0, home_shard=owner)
    store.sync_globals(T0)
    slot_before = int(store.gtable.owner_slot[store.gtable.get("glob_gk")])

    # Churn the owner shard's tiny table until gk's slot is stolen
    # (filler keys chosen to hash onto the owner shard).
    filler_keys = [
        f"fill{i}" for i in range(256)
        if shard_of_key(f"glob_fill{i}", store.n_shards) == owner
    ][:8]
    filler = [
        RateLimitRequest(name="glob", unique_key=k, hits=1,
                         limit=100, duration=60_000,
                         algorithm=Algorithm.TOKEN_BUCKET)
        for k in filler_keys
    ]
    store.apply(filler, T0 + 1, home_shard=owner)
    assert store.tables[owner].get_slot("glob_gk") is None  # evicted

    # More GLOBAL hits; the next sync must re-resolve (generation
    # bumped), reassign a slot, and still converge the counter.
    store.apply([mk("gk", hits=2, limit=10)], T0 + 2, home_shard=owner)
    res = store.sync_globals(T0 + 2)
    g = store.gtable.get("glob_gk")
    slot_after = int(store.gtable.owner_slot[g])
    assert store.tables[owner].get_slot("glob_gk") == slot_after
    bc = {b.key: b for b in res.broadcasts}
    assert "glob_gk" in bc
    # Eviction lost the first 3 hits (reference-grade loss); the
    # re-resolved slot carries the post-eviction state consistently.
    assert bc["glob_gk"].status.remaining == 8, (slot_before, slot_after, bc)


def test_sync_fast_path_steady_state_skips_verification():
    """With no mapping churn between syncs, the second pass must not
    touch the tables' lookup path at all (the O(active) -> O(changed)
    contract).  Pinned by COUNTING get_slot calls on the owner shard's
    table during the second sync — deleting the shard_clean fast path
    from _sync_globals_locked fails this test."""
    store = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    owner, _ = owner_and_other(store, "s1")
    store.apply([mk("s1", hits=1, limit=100)], T0, home_shard=owner)
    store.sync_globals(T0)
    gen_before = [t.generation for t in store.tables]

    # Hits only (no new keys): values change, mapping doesn't.
    store.apply([mk("s1", hits=1, limit=100)], T0 + 1, home_shard=owner)
    assert [t.generation for t in store.tables] == gen_before

    calls = {"n": 0}
    table = store.tables[owner]
    orig = table.get_slot

    def counting_get_slot(key):
        calls["n"] += 1
        return orig(key)

    table.get_slot = counting_get_slot
    try:
        store.sync_globals(T0 + 1)
    finally:
        del table.get_slot  # restore the bound method
    assert calls["n"] == 0, "clean shard must skip owner-slot verification"
    # And the resolved slot is still correct.
    g = store.gtable.get("glob_s1")
    assert store.tables[owner].get_slot("glob_s1") == int(
        store.gtable.owner_slot[g]
    )


def _count_calls(store):
    """Count `_drain_then_lock` and `_sync_fn` calls on one store."""
    calls = {"drain": 0, "sync_fn": 0}
    drain, sync_fn = store._drain_then_lock, store._sync_fn

    def counting_drain():
        calls["drain"] += 1
        return drain()

    def counting_sync_fn(*args):
        calls["sync_fn"] += 1
        return sync_fn(*args)

    store._drain_then_lock = counting_drain
    store._sync_fn = counting_sync_fn
    return calls


@pytest.mark.parametrize("where", ["owner", "non_owner_home_shard", "remote_global"])
def test_a_tick_runs_the_pass_only_when_a_global_lane_is_pending(where):
    """A GLOBAL apply raises the store's pending flag on every branch
    (owner dirt, non-owner ghits, remote-owner ghits); the next tick
    runs the pass and lowers it; a tick with no apply in between
    returns before the drain, the locks and the program."""
    store = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    owner, other = owner_and_other(store, "p1")
    assert not store._global_pending
    # A plain request never raises it.
    store.apply([mk("plain", behavior=0)], T0, home_shard=owner)
    assert not store._global_pending

    kwargs = {
        "owner": {"home_shard": owner},
        "non_owner_home_shard": {"home_shard": other},
        "remote_global": {"remote_global": True},
    }[where]
    r = store.apply([mk("p1", hits=4)], T0, **kwargs)[0]
    assert r.remaining == 6
    assert store._global_pending

    calls = _count_calls(store)
    res = store.sync_globals(T0 + 1)
    assert calls == {"drain": 1, "sync_fn": 1}
    assert res.did_work and not store._global_pending
    if where == "remote_global":
        # The host forwards the aggregated hits; nothing to broadcast.
        assert res.broadcast_count == 0
        (hit,) = res.remote_hits
        assert (hit.unique_key, hit.hits) == ("p1", 4)
    else:
        assert res.remote_hit_cols is None
        (b,) = res.broadcasts
        assert b.key == "glob_p1" and b.status.remaining == 6

    # Nothing applied since: the tick costs nothing and changes nothing.
    rep_expire = store.gtable.rep_expire.copy()
    res = store.sync_globals(T0 + 2)
    assert res.did_work is False
    assert res.broadcast_cols is None and res.remote_hit_cols is None
    assert calls == {"drain": 1, "sync_fn": 1}
    assert (store.gtable.rep_expire == rep_expire).all()

    # A received broadcast writes the replica columns directly: no pass owed.
    store.set_replica(
        UpdatePeerGlobal(
            key="glob_other", algorithm=Algorithm.TOKEN_BUCKET,
            status=RateLimitResponse(limit=10, remaining=3, reset_time=T0 + 60_000),
        ),
        T0 + 2,
    )
    assert not store._global_pending


def test_global_applies_racing_ticks_lose_no_hit():
    """GLOBAL hits applied on every branch while 200 ticks run beside
    them: a tick that reads the flag just before an apply raises it
    leaves the hits to the next tick, never drops them."""
    store = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    owner, other = owner_and_other(store, "race")
    limit = 1_000_000
    sent = {"local": 0, "remote": 0}
    stop = threading.Event()
    errors = []
    pauses = random.Random(26)

    def applier():
        i = 0
        try:
            while not stop.is_set():
                home = owner if i % 2 else other
                store.apply([mk("race", hits=1, limit=limit)], T0, home_shard=home)
                sent["local"] += 1
                store.apply([mk("far", hits=2, limit=limit)], T0, remote_global=True)
                sent["remote"] += 2
                i += 1
                time.sleep(pauses.uniform(0.0, 0.01))  # bursts, and idle ticks between
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    results = []
    t = threading.Thread(target=applier)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # hand the interpreter over mid-apply and mid-tick
    t.start()
    try:
        for _ in range(200):
            results.append(store.sync_globals(T0))
            time.sleep(0.001)
    finally:
        stop.set()
        t.join(timeout=60)
        sys.setswitchinterval(switch)
    assert not t.is_alive()
    results.append(store.sync_globals(T0))  # what the last applies left pending
    assert not errors, errors
    assert not store._global_pending

    passes = [r for r in results if r.did_work]
    assert passes and len(passes) < len(results)  # ticks of both kinds raced
    forwarded = sum(
        int(r.remote_hit_cols.hits.sum()) for r in passes if r.remote_hit_cols is not None
    )
    assert sent["remote"] > 0 and forwarded == sent["remote"]
    last = [b for r in passes for b in r.broadcasts if b.key == "glob_race"][-1]
    assert sent["local"] > 0 and last.status.remaining == limit - sent["local"]
