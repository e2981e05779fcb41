"""Two-tier (front/back) bucket table tests.

The front table absorbs every kernel scatter; LRU evictions demote live
rows to the device-resident back tier instead of dropping them, and
later lookups promote them back (native Table two-tier mode +
ops/buckets.apply_moves).  The semantic contract: a store with front F
and back B behaves EXACTLY like a plain store big enough to never evict
— state survives any number of demote/promote round trips — until the
back tier itself wraps (FIFO), which is the only true loss.
"""

import random

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.parallel.mesh import MeshBucketStore
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest, Status

from . import oracle
from .conftest import take_moves

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native runtime required"
)

T0 = 1_573_430_430_000


def mk(key, hits=1, limit=10, duration=60_000, algo=Algorithm.TOKEN_BUCKET):
    return RateLimitRequest(
        name="tt", unique_key=key, hits=hits, limit=limit, duration=duration,
        algorithm=algo,
    )


def test_native_table_demote_promote_records():
    t = native.NativeSlotTable(2)
    t.enable_back(8)
    s1, e1 = t.lookup_or_assign("a", T0)
    t.set_expire(s1, T0 + 60_000)  # materialize: only live rows demote
    s2, _ = t.lookup_or_assign("b", T0)
    t.set_expire(s2, T0 + 60_000)
    # capacity 2 full; "c" evicts LRU ("a"), demoting it
    s3, e3 = t.lookup_or_assign("c", T0)
    assert s3 == s1 and e3 is False
    np_, nd = t.move_counts()
    assert (np_, nd) == (0, 1)
    # "a" promotes back (evicting "b" -> demote)
    s4, e4 = t.lookup_or_assign("a", T0)
    assert e4 is True  # state survived: logical hit
    np_, nd = t.move_counts()
    assert (np_, nd) == (1, 2)
    pk, ps, pdst, ds, dd = take_moves(t)
    # the promo source is front slot s1's parked copy or a back slot;
    # the same-window re-promotion must be front-sourced (kind 1)
    assert pk[0] == 1 and pdst[0] == s4
    assert t.move_counts() == (0, 0)
    total, back_keys, demotions, promotions, back_ev = t.tier_stats
    assert demotions == 2 and promotions == 1 and back_ev == 0
    assert total == 3  # a, c in front; b in back


def test_native_table_expired_rows_drop_not_demote():
    t = native.NativeSlotTable(1)
    t.enable_back(4)
    s, _ = t.lookup_or_assign("x", T0)
    t.set_expire(s, T0 + 10)
    t.lookup_or_assign("y", T0 + 1000)  # x expired: plain drop
    assert t.move_counts() == (0, 0)
    assert t.tier_stats[1] == 0  # nothing in back


def test_native_table_back_fifo_eviction():
    t = native.NativeSlotTable(1)
    t.enable_back(2)
    for i, k in enumerate(["a", "b", "c", "d"]):
        s, _ = t.lookup_or_assign(k, T0)
        t.set_expire(s, T0 + 60_000)
    # a, b, c were demoted into a 2-slot FIFO back: a fell off
    total, back_keys, demotions, promotions, back_ev = t.tier_stats
    assert back_keys == 2 and back_ev == 1
    _, e = t.lookup_or_assign("a", T0)
    assert e is False  # truly lost


def churn_workload(rng, n_keys, steps):
    reqs = []
    for step in range(steps):
        k = rng.randrange(n_keys)
        reqs.append((f"k{k}", rng.choice([1, 1, 1, 2])))
    return reqs


@pytest.mark.parametrize("algo", [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET])
def test_two_tier_matches_unevicted_reference(algo):
    """front=8 forces constant demote/promote churn; responses must be
    byte-identical to the sequential reference, which never evicts."""
    rng = random.Random(11)
    two = MeshBucketStore(capacity_per_shard=2, back_capacity_per_shard=512)
    ref = oracle.OracleCache()
    now = T0
    for step in range(300):
        key = f"k{rng.randrange(40)}"
        r = mk(key, hits=rng.choice([0, 1, 1, 2]), algo=algo)
        now += rng.randrange(0, 500)
        got = two.apply([r], now)[0]
        want = oracle.apply(ref, r, now)
        assert (got.status, got.remaining, got.reset_time) == (
            want.status, want.remaining, want.reset_time,
        ), (step, key, got, want)
    # churn actually happened
    stats = [t.tier_stats for t in two.tables]
    assert sum(s[2] for s in stats) > 50, stats  # demotions
    assert sum(s[3] for s in stats) > 50, stats  # promotions
    two.check_consistency()


def test_two_tier_columnar_matches_unevicted_reference():
    """Churn ACROSS batches (shifting key windows): every batch's
    per-shard working set fits the front (the two-tier contract — a
    single batch whose unique keys exceed the front degrades to the
    planner's documented all-pending-slots fallback, reference-grade
    loss), but consecutive windows force constant demote/promote."""
    rng = np.random.RandomState(5)
    two = MeshBucketStore(capacity_per_shard=16, back_capacity_per_shard=2048)
    ref = oracle.OracleCache()  # never evicts
    now = T0
    for step in range(12):
        n = 200
        ids = (step * 40) + rng.randint(0, 80, size=n)
        keys = [f"c{k}" for k in ids]
        algo = (ids % 2).astype(np.int32)
        behavior = np.zeros(n, np.int32)
        hits = np.ones(n, np.int64)
        limit = np.full(n, 50, np.int64)
        duration = np.full(n, 60_000, np.int64)
        now += 700
        got = two.apply_columns(keys, algo, behavior, hits, limit, duration, now)
        want = [
            oracle.apply(ref, RateLimitRequest(
                name="c", unique_key=str(k), hits=1, limit=50,
                duration=60_000, algorithm=int(a)), now)
            for k, a in zip(ids, algo)
        ]
        for f in ("status", "remaining", "reset_time"):
            assert got[f].tolist() == [getattr(w, f) for w in want], (step, f)
    assert sum(t.tier_stats[2] for t in two.tables) > 100
    two.check_consistency()


def test_two_tier_snapshot_includes_back_rows():
    two = MeshBucketStore(capacity_per_shard=8, back_capacity_per_shard=256)
    now = T0
    for i in range(64):
        two.apply([mk(f"s{i}")], now)
    items = {it.key for it in two.snapshot_items()}
    # every live key must appear regardless of tier
    assert items == {f"tt_s{i}" for i in range(64)}


def test_two_tier_global_sync_promotes_owner_keys():
    """A GLOBAL key demoted by plain-traffic churn must still sync:
    sync_globals re-promotes owner keys before the collective."""
    two = MeshBucketStore(
        capacity_per_shard=4, g_capacity=16, back_capacity_per_shard=256
    )
    now = T0
    g = mk("gk")
    g = RateLimitRequest(
        name="tt", unique_key="gk", hits=1, limit=10, duration=60_000,
        behavior=Behavior.GLOBAL,
    )
    two.apply([g], now)
    # churn every shard's front table so gk demotes
    for i in range(64):
        two.apply([mk(f"churn{i}")], now + 1)
    res = two.sync_globals(now + 2)
    assert res.broadcast_count == 1
    st = res.broadcasts[0].status
    assert st.remaining == 9, st


def test_two_tier_rejects_store_spi():
    class DummyStore:
        def get(self, *a):
            return None

        def on_change(self, *a):
            pass

        def remove(self, *a):
            pass

    with pytest.raises(ValueError, match="Store SPI"):
        MeshBucketStore(
            capacity_per_shard=8, back_capacity_per_shard=64, store=DummyStore()
        )


def test_daemon_passes_back_cache_size_through():
    """GUBER_BACK_CACHE_SIZE must reach the store (round-4 drive found
    the daemon dropping it on the DaemonConfig -> ServiceConfig
    translation: the two-tier flag silently no-opped end-to-end)."""
    from gubernator_tpu.cluster import fast_test_behaviors
    from gubernator_tpu.config import setup_daemon_config
    from gubernator_tpu.daemon import Daemon

    conf = setup_daemon_config(env={
        "GUBER_CACHE_SIZE": "64", "GUBER_BACK_CACHE_SIZE": "4096",
    })
    conf.listen_address = "127.0.0.1:0"
    conf.behaviors = fast_test_behaviors()
    conf.peer_discovery_type = "static"
    d = Daemon(conf).start()
    try:
        assert d.service.store.back is not None
        assert d.service.store.back_capacity_per_shard == 4096 // 8
    finally:
        d.close()


def test_fifo_wrap_during_promotion_preserves_both_keys():
    """Round-4 review repro: promoting 'a' evicts 'b', whose demotion
    must NOT wrap the FIFO cursor onto a's in-flight back slot — that
    handed a the victim's expiry/row and destroyed b outright."""
    t = native.NativeSlotTable(1)
    t.enable_back(2)
    sa, _ = t.lookup_or_assign("a", T0)
    t.set_expire(sa, T0 + 60_000)
    sb, _ = t.lookup_or_assign("b", T0)  # evicts+demotes a
    t.set_expire(sb, T0 + 50_000)
    take_moves(t)
    sa2, ea = t.lookup_or_assign("a", T0)  # promote a; evict+demote b
    assert ea is True
    assert t.get_expire_bulk([sa2])[0] == T0 + 60_000  # a's OWN expiry
    # b survived into the back tier
    bkeys, _, bexp = t.back_entries()
    assert bkeys == ["b"] and bexp[0] == T0 + 50_000
    sb2, eb = t.lookup_or_assign("b", T0)
    assert eb is True


def test_back_capacity_one_degenerates_to_loss_not_corruption():
    t = native.NativeSlotTable(1)
    t.enable_back(1)
    sa, _ = t.lookup_or_assign("a", T0)
    t.set_expire(sa, T0 + 60_000)
    sb, _ = t.lookup_or_assign("b", T0)
    t.set_expire(sb, T0 + 50_000)
    take_moves(t)
    sa2, ea = t.lookup_or_assign("a", T0)  # promote a; b has nowhere to go
    assert ea is True
    assert t.get_expire_bulk([sa2])[0] == T0 + 60_000
    _, eb = t.lookup_or_assign("b", T0)
    assert eb is False  # b dropped (documented degenerate), not corrupted


def test_starved_fallback_never_serves_another_keys_row():
    """Round-4 review repro: with every front slot holding a pending
    promotion, the all-pending eviction fallback must CANCEL the
    promo (state loss) — demoting it would park the previous
    occupant's device row under the promoted key's name and later
    lookups would serve another key's counters."""
    t = native.NativeSlotTable(2)
    t.enable_back(8)
    for k in ("ka", "kb"):
        s, _ = t.lookup_or_assign(k, T0)
        t.set_expire(s, T0 + 60_000)
    for k in ("kc", "kd"):  # demote ka, kb
        s, _ = t.lookup_or_assign(k, T0)
        t.set_expire(s, T0 + 60_000)
    take_moves(t)
    # One window: promote ka and kb (both slots pending-promo), then a
    # miss forces the starved fallback.
    sa, ea = t.lookup_or_assign("ka", T0)
    sb, eb = t.lookup_or_assign("kb", T0)
    assert ea and eb
    se, ee = t.lookup_or_assign("ke", T0)
    assert ee is False
    pk, ps, pdst, ds, dd = take_moves(t)
    # the evicted promo was cancelled (src -1), and no demo record may
    # target a slot whose row never arrived
    live_promos = [(int(k), int(s), int(d))
                   for k, s, d in zip(pk, ps, pdst) if s >= 0]
    assert len(live_promos) == 1, (pk, ps, pdst)
    assert all(int(s) < 0 or int(dsl) != se for s, dsl in zip(ds, dd))
    # the evicted promoted key lost its state (loss, not corruption)
    _, e_again = t.lookup_or_assign(
        "ka" if se == sa else "kb", T0
    )
    assert e_again is False


# ---------------------------------------------------------------------
# A population that fits front + back loses nothing (PR 31)
# ---------------------------------------------------------------------
def _ask(t, key, now=T0):
    """One lookup of a live bucket: (front slot, whether its state survived)."""
    s, e = t.lookup_or_assign(key, now)
    t.set_expire(s, now + 60_000)
    return s, e


@pytest.mark.parametrize("front,back,drain_every", [(1, 2, 1), (4, 8, 3), (8, 16, 7)])
def test_keys_cycling_through_exactly_front_plus_back_slots_lose_nothing(front, back, drain_every):
    """Three keys in front 1 + back 2 (and 12 in 4 + 8, 24 in 8 + 16), a
    thousand laps, every lookup a front miss: each finds its state, whether
    the launch drains the moves after every lookup or a window holds several
    (fewer than the front has slots: a front slot whose promotion is still
    queued cannot be demoted).  With the ring cursor alone the back tier
    dropped a key a lap: the cursor landed on a live row while the slot the
    promotion had just left stood free."""
    t = native.NativeSlotTable(front)
    t.enable_back(back)
    keys = [f"k{i}" for i in range(front + back)]
    for k in keys:
        assert _ask(t, k)[1] is False
    take_moves(t)
    asked = 0
    for lap in range(1000):
        for k in keys:
            assert _ask(t, k)[1] is True, (lap, k)
            asked += 1
            if asked % drain_every == 0:
                take_moves(t)
    total, back_keys, demotions, promotions, back_ev = t.tier_stats
    assert (total, back_keys, back_ev) == (front + back, back, 0)
    assert promotions == asked and demotions == back + asked
    assert t.evictions == 0 and t.front_evictions == demotions and len(t) == front


def test_three_keys_cycling_through_the_store_equal_the_oracle():
    """The same cycle through the device: the swap (a demoted row written to
    the back slot the promoted row is read from, in one move program) keeps
    both rows."""
    from .conftest import one_device_store

    two = one_device_store(1, back_capacity_per_shard=2)
    ref = oracle.OracleCache()
    now = T0
    for step in range(120):
        r = mk("xyz"[step % 3], limit=1000)
        now += 7
        got, want = two.apply([r], now)[0], oracle.apply(ref, r, now)
        assert (got.status, got.remaining, got.reset_time) == (
            want.status, want.remaining, want.reset_time), step
    assert two.tables[0].tier_stats[4] == 0 and two.size() == 3  # back_evictions
    two.check_consistency()


def test_a_freed_back_slot_is_taken_before_a_live_one():
    """Front 1, back 2, both back slots live: a removal frees one, and the
    next demotion takes it instead of evicting the other."""
    t = native.NativeSlotTable(1)
    t.enable_back(2)
    for k in "abc":
        _ask(t, k)
    take_moves(t)
    assert sorted(t.back_entries()[0]) == ["a", "b"]
    t.remove("a")
    _ask(t, "d")  # demotes c: into a's slot
    assert sorted(t.back_entries()[0]) == ["b", "c"]
    assert t.tier_stats[4] == 0 and t.evictions == 0
    for k in "bc":
        assert _ask(t, k)[1] is True
        take_moves(t)


def test_an_expired_back_row_gives_its_slot_to_the_next_demotion():
    t = native.NativeSlotTable(1)
    t.enable_back(2)
    s, _ = t.lookup_or_assign("a", T0)
    t.set_expire(s, T0 + 10)
    for k in "bc":
        _ask(t, k)
    take_moves(t)
    assert sorted(t.back_entries()[0]) == ["a", "b"]
    # a has expired in the back: asked for again it is a plain create, and c,
    # demoted to make room for it, takes the slot a's dead row gave up.
    assert _ask(t, "a", T0 + 1000)[1] is False
    assert sorted(t.back_entries()[0]) == ["b", "c"]
    assert t.tier_stats[4] == 0 and t.evictions == 0


def test_more_keys_than_slots_still_lose_the_oldest():
    t = native.NativeSlotTable(1)
    t.enable_back(2)
    for k in "abcd":
        _ask(t, k)
    assert t.tier_stats[4] == 1 and sorted(t.back_entries()[0]) == ["b", "c"]
    assert _ask(t, "a")[1] is False


def test_evictions_count_lost_buckets_and_sizes_count_both_tiers():
    """`evictions` (what `gubernator_occupancy_evictions` and /debug/status
    serve) grows with buckets lost, not with demotions; `store.size()`
    (`gubernator_cache_size`) is every resident bucket; `len(table)` and the
    occupancy ratio stay the front's."""
    from .conftest import one_device_store

    two = one_device_store(4, back_capacity_per_shard=8)
    now = T0
    for i in range(12):
        two.apply([mk(f"r{i}", limit=100)], now)
    (row,) = two.occupancy_stats()
    assert two.size() == 12 and len(two.tables[0]) == 4
    assert (row["used"], row["capacity"], row["back_used"], row["back_capacity"]) == (4, 4, 8, 8)
    assert (row["demotions"], row["promotions"], row["back_evictions"], row["evictions"]) == (8, 0, 0, 0)
    assert two.tables[0].front_evictions == 8
    two.apply([mk("r0", limit=100)], now)  # promoted; its victim takes the slot it left
    two.apply([mk("one-too-many", limit=100)], now)  # 13 keys in 12 slots: the oldest goes
    (row,) = two.occupancy_stats()
    assert (row["promotions"], row["back_evictions"], row["evictions"]) == (1, 1, 1)
    assert two.size() == 12
    two.check_consistency()
    one = one_device_store(4)
    for i in range(6):
        one.apply([mk(f"r{i}")], now)
    (row,) = one.occupancy_stats()
    assert row["evictions"] == 2 and one.size() == 4 and "demotions" not in row


def test_take_moves_into_refuses_a_block_too_small_and_drains_nothing():
    t = native.NativeSlotTable(1)
    t.enable_back(8)
    for k in "abcd":
        _ask(t, k)
    assert t.move_counts() == (0, 3)
    small = np.full((5, 2), -1, np.int32)
    assert t.take_moves_into(small) is None and t.move_counts() == (0, 3)
    block = np.zeros((5, 4), np.int32)
    block[[1, 3]] = -1
    assert t.take_moves_into(block) == (0, 3)
    assert block[3].tolist()[3] == -1 and (block[3, :3] >= 0).all()
    assert sorted(block[4, :3].tolist()) == [0, 1, 2] and t.move_counts() == (0, 0)
