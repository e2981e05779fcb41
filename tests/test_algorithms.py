"""Algorithm semantics tests: frozen-clock tables ported from the
reference's functional suite, plus randomized differential testing of the
vectorized kernel against the sequential oracle.

Table sources: functional_test.go TestTokenBucket (:108-167),
TestOverTheLimit (:60-106), TestTokenBucketGregorian (:169-242),
TestLeakyBucket (:244-348), TestLeakyBucketGregorian (:350-413),
TestChangeLimit (:548-641), TestResetRemaining (:643-713),
TestLeakyBucketDivBug (:784-824).
"""

import random

import pytest

from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    RateLimitRequest,
    Status,
    MILLISECOND,
    SECOND,
    MINUTE,
)
from gubernator_tpu.utils.clock import Clock
from gubernator_tpu.utils import gregorian

from . import oracle
from .conftest import one_device_store

T0 = 1_573_430_430_000  # 2019-11-11T00:00:30Z


def mk(name="t", key="account:1234", hits=1, limit=10, duration=SECOND, algo=Algorithm.TOKEN_BUCKET, behavior=0):
    return RateLimitRequest(
        name=name, unique_key=key, hits=hits, limit=limit,
        duration=duration, algorithm=algo, behavior=behavior,
    )


def one(store, req, now):
    return store.apply([req], now)[0]


def test_over_the_limit(make_store):
    store = make_store(64)
    now = T0
    expect = [(1, Status.UNDER_LIMIT), (0, Status.UNDER_LIMIT), (0, Status.OVER_LIMIT)]
    for remaining, status in expect:
        r = one(store, mk(name="test_over_limit", limit=2, duration=9 * SECOND), now)
        assert r.status == status
        assert r.remaining == remaining
        assert r.limit == 2
        assert r.reset_time != 0


def test_token_bucket(make_store):
    store = make_store(64)
    clock = Clock()
    clock.freeze(T0)
    table = [
        (1, Status.UNDER_LIMIT, 0),
        (0, Status.UNDER_LIMIT, 100),
        (1, Status.UNDER_LIMIT, 0),  # expired after 100ms > 5ms duration
    ]
    for remaining, status, sleep_ms in table:
        r = one(store, mk(name="test_token_bucket", limit=2, duration=5 * MILLISECOND), clock.now_ms())
        assert r.status == status
        assert r.remaining == remaining
        assert r.reset_time != 0
        clock.advance(sleep_ms)


def test_token_bucket_gregorian(make_store):
    store = make_store(64)
    clock = Clock()
    clock.freeze(T0)
    table = [
        (1, 59, Status.UNDER_LIMIT, 0),
        (1, 58, Status.UNDER_LIMIT, 0),
        (58, 0, Status.UNDER_LIMIT, 0),
        (1, 0, Status.OVER_LIMIT, 61 * SECOND),
        (0, 60, Status.UNDER_LIMIT, 0),
    ]
    for hits, remaining, status, sleep_ms in table:
        req = mk(
            name="test_token_bucket_greg", key="account:12345", hits=hits, limit=60,
            duration=gregorian.GREGORIAN_MINUTES, behavior=Behavior.DURATION_IS_GREGORIAN,
        )
        r = one(store, req, clock.now_ms())
        assert r.status == status, r
        assert r.remaining == remaining
        assert r.limit == 60
        assert r.reset_time != 0
        clock.advance(sleep_ms)


def test_leaky_bucket(make_store):
    store = make_store(64)
    clock = Clock()
    clock.freeze(T0)
    table = [
        # hits, remaining, status, sleep_ms
        (1, 9, Status.UNDER_LIMIT, SECOND),
        (1, 8, Status.UNDER_LIMIT, SECOND),
        (1, 7, Status.UNDER_LIMIT, 1500),
        (0, 8, Status.UNDER_LIMIT, 3 * SECOND),
        (0, 9, Status.UNDER_LIMIT, 0),
        (9, 0, Status.UNDER_LIMIT, 0),
        (1, 0, Status.OVER_LIMIT, 3 * SECOND),
        (0, 1, Status.UNDER_LIMIT, 60 * SECOND),
        (0, 10, Status.UNDER_LIMIT, SECOND),
    ]
    for hits, remaining, status, sleep_ms in table:
        req = mk(
            name="test_leaky_bucket", hits=hits, limit=10, duration=30 * SECOND,
            algo=Algorithm.LEAKY_BUCKET,
        )
        now = clock.now_ms()
        r = one(store, req, now)
        assert r.status == status, (r, hits)
        assert r.remaining == remaining
        assert r.limit == 10
        # rate = 30s/10 = 3s per token (functional_test.go:334)
        assert r.reset_time // 1000 == now // 1000 + 3
        clock.advance(sleep_ms)


def test_leaky_bucket_gregorian(make_store):
    store = make_store(64)
    clock = Clock()
    clock.freeze(T0)
    table = [
        (1, 59, Status.UNDER_LIMIT, 500),
        (1, 58, Status.UNDER_LIMIT, SECOND),
        (1, 58, Status.UNDER_LIMIT, 0),  # leaked one back at 1.5s elapsed
    ]
    for hits, remaining, status, sleep_ms in table:
        req = mk(
            name="test_leaky_bucket_greg", key="account:12345", hits=hits, limit=60,
            duration=gregorian.GREGORIAN_MINUTES, algo=Algorithm.LEAKY_BUCKET,
            behavior=Behavior.DURATION_IS_GREGORIAN,
        )
        now = clock.now_ms()
        r = one(store, req, now)
        assert r.status == status
        assert r.remaining == remaining
        assert r.limit == 60
        assert r.reset_time > T0 // 1000
        clock.advance(sleep_ms)


def test_change_limit(make_store):
    store = make_store(64)
    now = T0
    table = [
        # algorithm, limit, expected_remaining
        (Algorithm.TOKEN_BUCKET, 100, 99),
        (Algorithm.TOKEN_BUCKET, 100, 98),
        (Algorithm.TOKEN_BUCKET, 10, 7),  # 98 + (10-100) = 8, hit -> 7
        (Algorithm.TOKEN_BUCKET, 10, 6),
        (Algorithm.TOKEN_BUCKET, 200, 195),  # 6 + 190 = 196, hit -> 195
        (Algorithm.LEAKY_BUCKET, 100, 99),  # algo switch resets
        (Algorithm.LEAKY_BUCKET, 10, 9),  # clamp 99 -> 10, hit -> 9
        (Algorithm.LEAKY_BUCKET, 10, 8),
    ]
    for algo, limit, remaining in table:
        r = one(store, mk(name="test_change_limit", limit=limit, duration=9000, algo=algo), now)
        assert r.status == Status.UNDER_LIMIT
        assert r.remaining == remaining, (algo, limit, remaining, r)
        assert r.limit == limit
        assert r.reset_time != 0


def test_reset_remaining(make_store):
    store = make_store(64)
    now = T0
    table = [
        (Behavior.BATCHING, 99),
        (Behavior.BATCHING, 98),
        (Behavior.RESET_REMAINING, 100),
        (Behavior.BATCHING, 99),
    ]
    for behavior, remaining in table:
        r = one(store, mk(name="test_reset_remaining", limit=100, duration=9000, behavior=behavior), now)
        assert r.status == Status.UNDER_LIMIT
        assert r.remaining == remaining


def test_leaky_bucket_div_bug(make_store):
    store = make_store(64)
    now = T0
    r = one(store, mk(name="div", limit=2000, duration=1000, algo=Algorithm.LEAKY_BUCKET), now)
    assert r.status == Status.UNDER_LIMIT
    assert r.remaining == 1999
    assert r.limit == 2000
    r = one(store, mk(name="div", hits=100, limit=2000, duration=1000, algo=Algorithm.LEAKY_BUCKET), now)
    assert r.remaining == 1899
    assert r.limit == 2000


def test_hits_greater_than_limit_on_create(make_store):
    """algorithms.go:161-166 / :318-323"""
    store = make_store(64)
    now = T0
    r = one(store, mk(name="big", hits=1000, limit=100, duration=9000), now)
    assert r.status == Status.OVER_LIMIT
    assert r.remaining == 100  # token keeps remaining = limit
    r = one(store, mk(name="bigl", hits=1000, limit=100, duration=9000, algo=Algorithm.LEAKY_BUCKET), now)
    assert r.status == Status.OVER_LIMIT
    assert r.remaining == 0  # leaky drains to 0


def test_over_limit_does_not_mutate(make_store):
    """algorithms.go:126-130: a rejected over-sized request leaves state."""
    store = make_store(64)
    now = T0
    one(store, mk(name="nm", hits=1, limit=100, duration=9000), now)  # rem 99
    r = one(store, mk(name="nm", hits=1000, limit=100, duration=9000), now)
    assert r.status == Status.OVER_LIMIT
    assert r.remaining == 99
    r = one(store, mk(name="nm", hits=99, limit=100, duration=9000), now)
    assert r.status == Status.UNDER_LIMIT
    assert r.remaining == 0


def test_expiry_boundary_exact_ms(make_store):
    """At now == ExpireAt the bucket is still live (cache.go:151 is a
    strict `<`); one ms later it recreates."""
    store = make_store(64)
    clock = Clock()
    clock.freeze(T0)
    req = mk(name="edge", hits=2, limit=2, duration=1000)
    r = one(store, req, clock.now_ms())
    assert r.remaining == 0
    clock.advance(1000)  # now == ExpireAt exactly
    r = one(store, mk(name="edge", hits=1, limit=2, duration=1000), clock.now_ms())
    assert r.status == Status.OVER_LIMIT  # still the drained bucket
    clock.advance(1)
    r = one(store, mk(name="edge", hits=1, limit=2, duration=1000), clock.now_ms())
    assert r.status == Status.UNDER_LIMIT and r.remaining == 1


def test_leaky_nonrepresentable_rate(make_store):
    """Non-binary-representable rates (duration=1000, limit=30): the
    kernel computes leak = elapsed*limit/duration exactly, where the
    reference double-rounds through float64 and can under-count by one
    token at exact multiples.  Pin exactness and the <=1-token bound
    vs the float oracle."""
    store = make_store(64)
    ocache = oracle.OracleCache()
    clock = Clock()
    clock.freeze(T0)
    req = mk(name="nr", hits=30, limit=30, duration=1000, algo=Algorithm.LEAKY_BUCKET)
    now = clock.now_ms()
    got, want = one(store, req, now), oracle.apply(ocache, req, now)
    assert got.remaining == want.remaining == 0
    clock.advance(500)  # exact leak = 500*30/1000 = 15; float64: 14.999...
    q = mk(name="nr", hits=0, limit=30, duration=1000, algo=Algorithm.LEAKY_BUCKET)
    now = clock.now_ms()
    got, want = one(store, q, now), oracle.apply(ocache, q, now)
    assert got.remaining == 15  # exact integer math
    assert want.remaining == 14  # reference float64 under-counts from 0.0
    assert abs(got.remaining - want.remaining) <= 1


def test_leaky_huge_limit_no_overflow(make_store):
    """elapsed*limit exceeding int64 must not wrap (128-bit muldiv)."""
    store = make_store(64)
    clock = Clock()
    clock.freeze(T0)
    month = 30 * 24 * 3600 * 1000  # 2.59e9 ms
    big = 2**42
    req = mk(name="huge", hits=big, limit=big, duration=month, algo=Algorithm.LEAKY_BUCKET)
    r = one(store, req, clock.now_ms())
    assert r.remaining == 0
    clock.advance(month // 2)  # half the period -> half the bucket leaks back
    r = one(store, mk(name="huge", hits=0, limit=big, duration=month, algo=Algorithm.LEAKY_BUCKET), clock.now_ms())
    assert r.status == Status.UNDER_LIMIT
    assert abs(r.remaining - big // 2) <= 1


def test_duplicate_keys_in_one_batch(make_store):
    """Duplicate keys in a single batch behave like sequential requests."""
    store = make_store(64)
    now = T0
    reqs = [mk(name="dup", hits=3, limit=10, duration=9000) for _ in range(4)]
    resps = store.apply(reqs, now)
    assert [r.remaining for r in resps] == [7, 4, 1, 1]
    assert [r.status for r in resps] == [
        Status.UNDER_LIMIT, Status.UNDER_LIMIT, Status.UNDER_LIMIT, Status.OVER_LIMIT,
    ]


def test_eviction_collision_with_reset_does_not_drop_new_key():
    """Regression: a RESET_REMAINING lane whose slot gets evicted and
    remapped mid-batch must not delete the new key's mapping when its
    removed-flag commits (key-guarded commit)."""
    store = one_device_store(2)
    now = T0
    one(store, mk(name="x", key="A", hits=1, limit=10, duration=9000), now)
    one(store, mk(name="x", key="B", hits=1, limit=10, duration=9000), now)
    resps = store.apply(
        [
            mk(name="x", key="A", hits=1, limit=10, duration=9000,
               behavior=Behavior.RESET_REMAINING),
            mk(name="x", key="B", hits=1, limit=10, duration=9000),
            mk(name="x", key="C", hits=1, limit=10, duration=9000),  # evicts A's slot
        ],
        now,
    )
    assert [r.remaining for r in resps] == [10, 8, 9]
    # C must still be mapped: another hit continues its bucket.
    r = one(store, mk(name="x", key="C", hits=1, limit=10, duration=9000), now)
    assert r.remaining == 8


def test_padding_lanes_do_not_corrupt_last_slot():
    """Regression: jax .at[-1] wraps, so padding lanes (slot=-1) used to
    scatter garbage into the table's last slot."""
    store = one_device_store(2)
    now = T0
    one(store, mk(name="p", key="K0", hits=1, limit=10, duration=9000), now)
    one(store, mk(name="p", key="K1", hits=1, limit=10, duration=9000), now)  # slot 1 (last)
    # Another padded batch touching only K0 must leave K1's bucket intact.
    one(store, mk(name="p", key="K0", hits=1, limit=10, duration=9000), now)
    r = one(store, mk(name="p", key="K1", hits=1, limit=10, duration=9000), now)
    assert r.remaining == 8


def test_lru_eviction():
    store = one_device_store(4)
    now = T0
    for i in range(6):
        one(store, mk(name="ev", key=f"k{i}", hits=1, limit=10, duration=9000), now)
    assert store.size() == 4
    assert store.tables[0].evictions == 2
    # k0 was evicted; hitting it again recreates a fresh bucket
    r = one(store, mk(name="ev", key="k0", hits=1, limit=10, duration=9000), now)
    assert r.remaining == 9


@pytest.mark.parametrize("algo", [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET])
def test_differential_vs_oracle(algo, make_store):
    """Randomized sequences must match the sequential reference oracle."""
    rng = random.Random(1234 + algo)
    store = make_store(256)
    ocache = oracle.OracleCache()
    clock = Clock()
    clock.freeze(T0)
    keys = [f"k{i}" for i in range(8)]
    for step in range(300):
        key = rng.choice(keys)
        behavior = 0
        if rng.random() < 0.05:
            behavior |= Behavior.RESET_REMAINING
        req = mk(
            name="diff",
            key=key,
            hits=rng.choice([0, 1, 1, 2, 5, 10, 50]),
            limit=rng.choice([5, 10, 100]),
            duration=rng.choice([1000, 5000, 60_000]),
            algo=algo,
            behavior=behavior,
        )
        now = clock.now_ms()
        got = one(store, req, now)
        want = oracle.apply(ocache, req, now)
        assert got.status == want.status, (step, req, got, want)
        assert got.limit == want.limit, (step, req, got, want)
        assert got.remaining == want.remaining, (step, req, got, want)
        assert got.reset_time == want.reset_time, (step, req, got, want)
        clock.advance(rng.choice([0, 0, 1, 7, 100, 1500, 6000]))


def test_differential_mixed_algo_switches(make_store):
    """Algorithm switches mid-stream reset buckets (algorithms.go:54-62)."""
    rng = random.Random(99)
    store = make_store(256)
    ocache = oracle.OracleCache()
    clock = Clock()
    clock.freeze(T0)
    for step in range(200):
        req = mk(
            name="sw",
            key=f"k{rng.randrange(4)}",
            hits=rng.choice([0, 1, 2]),
            limit=10,
            duration=5000,
            algo=rng.choice([Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]),
        )
        now = clock.now_ms()
        got = one(store, req, now)
        want = oracle.apply(ocache, req, now)
        assert (got.status, got.remaining, got.reset_time) == (
            want.status, want.remaining, want.reset_time,
        ), (step, req)
        clock.advance(rng.choice([0, 3, 50, 700]))
