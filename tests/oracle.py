"""Reference-semantics oracle for differential testing.

A deliberately unoptimized, line-faithful Python model of the reference's
sequential algorithms (`algorithms.go:24-180` tokenBucket,
`algorithms.go:183-336` leakyBucket, with the cache expiry rules of
`cache.go:138-163`).  The production kernel (gubernator_tpu.ops.buckets)
is validated against this model on randomized request sequences; the
oracle itself is validated by the pinned tables ported from
functional_test.go.

The one intentional divergence mirrored here: the production code uses
`now + duration` for the leaky-bucket expiry refresh where the reference
has the `now * duration` bug (algorithms.go:287), so the oracle does too.

What the callers' routing bits do to an OWNER's answer: nothing, and so
the oracle reads none of them (`ROUTING_BEHAVIOR`, held by
tests/test_mixed_cell.py).  Upstream's owner applies a request to its
own bucket whatever they say (`getRateLimit`, gubernator.go:330-345) and
only then looks at them:

- NO_BATCHING (1) is read by the CLIENT side of a forward alone
  (peer_client.go: send now, not with the next batch): it changes when a
  check is dispatched, never what it answers.
- GLOBAL (2): the owner answers from its bucket, exactly, and queues the
  key's status for the other peers (`QueueUpdate`, gubernator.go:339-341).
  Only a NON-owner's answer differs (its replica's, stale by a sync
  window): that is not an owner's answer and this oracle does not model it.
- MULTI_REGION (16): the owner answers from its bucket, exactly, and
  queues the hits for the other regions (`QueueHits`, gubernator.go:343-345).
"""

from __future__ import annotations

import datetime as _dt
from dataclasses import dataclass, field
from typing import Dict, Optional

from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest, RateLimitResponse, Status, has_behavior
from gubernator_tpu.utils import gregorian


@dataclass
class TokenItem:
    limit: int
    duration: int
    remaining: int
    created_at: int
    status: int = Status.UNDER_LIMIT


@dataclass
class LeakyItem:
    limit: int
    duration: int
    remaining: float
    updated_at: int


@dataclass
class Item:
    algorithm: int
    key: str
    value: object
    expire_at: int


class OracleCache:
    def __init__(self):
        self.items: Dict[str, Item] = {}

    def get(self, key: str, now: int) -> Optional[Item]:
        item = self.items.get(key)
        if item is None:
            return None
        if item.expire_at < now:  # strict expiry == miss (cache.go:151)
            del self.items[key]
            return None
        return item

    def add(self, item: Item):
        self.items[item.key] = item

    def remove(self, key: str):
        self.items.pop(key, None)


def _now_dt(now: int) -> _dt.datetime:
    return _dt.datetime.fromtimestamp(now / 1000.0, tz=_dt.timezone.utc)


def token_bucket(c: OracleCache, r: RateLimitRequest, now: int) -> RateLimitResponse:
    key = r.hash_key()
    item = c.get(key, now)

    if item is not None:
        if has_behavior(r.behavior, Behavior.RESET_REMAINING):
            c.remove(key)
            return RateLimitResponse(
                status=Status.UNDER_LIMIT, limit=r.limit, remaining=r.limit, reset_time=0
            )
        if not isinstance(item.value, TokenItem):
            c.remove(key)
            return token_bucket(c, r, now)
        t = item.value

        if t.limit != r.limit:
            t.remaining += r.limit - t.limit
            if t.remaining < 0:
                t.remaining = 0
            t.limit = r.limit

        rl = RateLimitResponse(
            status=t.status, limit=r.limit, remaining=t.remaining, reset_time=item.expire_at
        )

        if t.duration != r.duration:
            expire = t.created_at + r.duration
            if has_behavior(r.behavior, Behavior.DURATION_IS_GREGORIAN):
                expire = gregorian.gregorian_expiration(_now_dt(now), r.duration)
            if expire < now:
                c.remove(key)
                return token_bucket(c, r, now)
            item.expire_at = expire
            rl.reset_time = expire

        if r.hits == 0:
            return rl
        if rl.remaining == 0:
            rl.status = Status.OVER_LIMIT
            t.status = rl.status
            return rl
        if t.remaining == r.hits:
            t.remaining = 0
            rl.remaining = 0
            return rl
        if r.hits > t.remaining:
            rl.status = Status.OVER_LIMIT
            return rl
        t.remaining -= r.hits
        rl.remaining = t.remaining
        return rl

    expire = now + r.duration
    if has_behavior(r.behavior, Behavior.DURATION_IS_GREGORIAN):
        expire = gregorian.gregorian_expiration(_now_dt(now), r.duration)

    t = TokenItem(limit=r.limit, duration=r.duration, remaining=r.limit - r.hits, created_at=now)
    rl = RateLimitResponse(
        status=Status.UNDER_LIMIT, limit=r.limit, remaining=t.remaining, reset_time=expire
    )
    if r.hits > r.limit:
        rl.status = Status.OVER_LIMIT
        rl.remaining = r.limit
        t.remaining = r.limit
    c.add(Item(algorithm=r.algorithm, key=key, value=t, expire_at=expire))
    return rl


def leaky_bucket(c: OracleCache, r: RateLimitRequest, now: int) -> RateLimitResponse:
    key = r.hash_key()
    item = c.get(key, now)

    if item is not None:
        if not isinstance(item.value, LeakyItem):
            c.remove(key)
            return leaky_bucket(c, r, now)
        b = item.value

        if has_behavior(r.behavior, Behavior.RESET_REMAINING):
            b.remaining = float(r.limit)
        b.limit = r.limit
        b.duration = r.duration

        duration = r.duration
        rate = float(duration) / float(r.limit)
        if has_behavior(r.behavior, Behavior.DURATION_IS_GREGORIAN):
            d = gregorian.gregorian_duration(_now_dt(now), r.duration)
            expire = gregorian.gregorian_expiration(_now_dt(now), r.duration)
            rate = float(d) / float(r.limit)
            duration = expire - now

        elapsed = now - b.updated_at
        leak = float(elapsed) / rate
        if int(leak) > 0:
            b.remaining += leak
            b.updated_at = now
        if int(b.remaining) > b.limit:
            b.remaining = float(b.limit)

        rl = RateLimitResponse(
            limit=b.limit,
            remaining=int(b.remaining),
            status=Status.UNDER_LIMIT,
            reset_time=now + int(rate),
        )
        if int(b.remaining) == 0:
            rl.status = Status.OVER_LIMIT
            return rl
        if int(b.remaining) == r.hits:
            b.remaining -= float(r.hits)
            rl.remaining = 0
            return rl
        if r.hits > int(b.remaining):
            rl.status = Status.OVER_LIMIT
            return rl
        if r.hits == 0:
            return rl
        b.remaining -= float(r.hits)
        rl.remaining = int(b.remaining)
        item.expire_at = now + duration  # deliberate divergence (see module doc)
        return rl

    duration = r.duration
    if has_behavior(r.behavior, Behavior.DURATION_IS_GREGORIAN):
        expire = gregorian.gregorian_expiration(_now_dt(now), r.duration)
        duration = expire - now

    b = LeakyItem(
        remaining=float(r.limit - r.hits), limit=r.limit, duration=duration, updated_at=now
    )
    rl = RateLimitResponse(
        status=Status.UNDER_LIMIT,
        limit=r.limit,
        remaining=r.limit - r.hits,
        reset_time=now + duration // max(r.limit, 1),
    )
    if r.hits > r.limit:
        rl.status = Status.OVER_LIMIT
        rl.remaining = 0
        b.remaining = 0.0
    c.add(Item(algorithm=r.algorithm, key=key, value=b, expire_at=now + duration))
    return rl


# The bits above: an owner's answer is the same with any of them set.
ROUTING_BEHAVIOR = Behavior.NO_BATCHING | Behavior.GLOBAL | Behavior.MULTI_REGION


def apply(c: OracleCache, r: RateLimitRequest, now: int) -> RateLimitResponse:
    if r.algorithm == Algorithm.LEAKY_BUCKET:
        return leaky_bucket(c, r, now)
    return token_bucket(c, r, now)
