"""Store/Loader SPI tests: exact call-count sequences from
store_test.go:125-287 (TestStore) and :75-123 (TestLoader), applied at
the store level, over one device and over four shards (`make_store`)."""

import pytest

from gubernator_tpu.store import (
    CacheItem,
    LeakyBucketItem,
    MockLoader,
    MockStore,
    TokenBucketItem,
)
from gubernator_tpu.types import Algorithm, RateLimitRequest, Status, SECOND

T0 = 1_573_430_430_000


def mk(algo, hits=1):
    return RateLimitRequest(
        name="test_over_limit", unique_key="account:1234", hits=hits,
        limit=10, duration=SECOND, algorithm=algo,
    )


def get_remaining(item):
    return int(item.value.remaining)


@pytest.mark.parametrize(
    "algo,switch_algo,preload,first_rem,first_status,second_rem,second_status",
    [
        (Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET, False, 9, Status.UNDER_LIMIT, 8, Status.UNDER_LIMIT),
        (Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET, True, 0, Status.UNDER_LIMIT, 0, Status.OVER_LIMIT),
        (Algorithm.LEAKY_BUCKET, Algorithm.TOKEN_BUCKET, False, 9, Status.UNDER_LIMIT, 8, Status.UNDER_LIMIT),
        (Algorithm.LEAKY_BUCKET, Algorithm.TOKEN_BUCKET, True, 0, Status.UNDER_LIMIT, 0, Status.OVER_LIMIT),
    ],
    ids=["token-empty", "token-preloaded", "leaky-empty", "leaky-preloaded"],
)
def test_store_call_sequences(make_store, algo, switch_algo, preload, first_rem, first_status, second_rem, second_status):
    store = MockStore()
    shard = make_store(64, store=store)
    req = mk(algo)

    if preload:
        if algo == Algorithm.TOKEN_BUCKET:
            value = TokenBucketItem(limit=10, duration=SECOND, created_at=T0, remaining=1)
        else:
            value = LeakyBucketItem(limit=10, duration=SECOND, updated_at=T0, remaining=1.0)
        store.cache_items[req.hash_key()] = CacheItem(
            algorithm=algo, key=req.hash_key(), value=value, expire_at=T0 + SECOND
        )

    assert store.called["OnChange()"] == 0 and store.called["Get()"] == 0

    r = shard.apply([req], T0)[0]
    assert r.error == ""
    assert r.remaining == first_rem
    assert r.limit == 10
    assert r.status == first_status
    assert store.called["OnChange()"] == 1
    assert store.called["Get()"] == 1
    assert get_remaining(store.cache_items[req.hash_key()]) == first_rem

    r = shard.apply([req], T0)[0]
    assert r.remaining == second_rem
    assert r.status == second_status
    assert store.called["OnChange()"] == 2
    assert store.called["Get()"] == 1  # cache hit: no store read
    assert get_remaining(store.cache_items[req.hash_key()]) == second_rem

    # Algorithm switch: Remove + re-Get + OnChange (algorithms.go:54-62).
    r = shard.apply([mk(switch_algo)], T0)[0]
    assert store.called["Remove()"] == 1
    assert store.called["OnChange()"] == 3
    assert store.called["Get()"] == 2
    assert store.cache_items[req.hash_key()].algorithm == switch_algo


def test_reset_remaining_removes_from_store(make_store):
    """algorithms.go:36-47: token RESET_REMAINING removes cache + store."""
    from gubernator_tpu.types import Behavior

    store = MockStore()
    shard = make_store(64, store=store)
    shard.apply([mk(Algorithm.TOKEN_BUCKET)], T0)
    assert store.called["OnChange()"] == 1
    req = mk(Algorithm.TOKEN_BUCKET)
    req.behavior = Behavior.RESET_REMAINING
    r = shard.apply([req], T0)[0]
    assert r.remaining == 10
    assert store.called["Remove()"] == 1
    assert req.hash_key() not in store.cache_items
    assert store.called["OnChange()"] == 1  # reset lane fires no OnChange


def test_loader_roundtrip(make_store):
    """TestLoader (store_test.go:75-123): load at start, save at stop."""
    loader = MockLoader()
    shard = make_store(64)
    for item in loader.load():
        shard.load_item(item)
    assert loader.called["Load()"] == 1 and loader.called["Save()"] == 0

    req = RateLimitRequest(
        name="test_over_limit", unique_key="account:1234", hits=1,
        limit=2, duration=SECOND, algorithm=Algorithm.TOKEN_BUCKET,
    )
    r = shard.apply([req], T0)[0]
    assert r.error == ""

    loader.save(shard.snapshot_items())
    assert loader.called["Save()"] == 1
    assert len(loader.cache_items) == 1
    item = loader.cache_items[0]
    assert isinstance(item.value, TokenBucketItem)
    assert item.value.limit == 2
    assert item.value.remaining == 1
    assert item.value.status == Status.UNDER_LIMIT


def test_loader_preload_then_hit(make_store):
    """Preloaded items serve subsequent traffic."""
    loader = MockLoader()
    loader.cache_items.append(
        CacheItem(
            algorithm=Algorithm.TOKEN_BUCKET,
            key="ns_k",
            value=TokenBucketItem(limit=10, duration=60_000, remaining=4, created_at=T0),
            expire_at=T0 + 60_000,
        )
    )
    shard = make_store(64)
    for item in loader.load():
        shard.load_item(item)
    req = RateLimitRequest(name="ns", unique_key="k", hits=1, limit=10, duration=60_000)
    r = shard.apply([req], T0 + 5)[0]
    assert r.remaining == 3
