"""The plain reference: a copy of `tests/oracle.py`'s line-faithful model of
upstream Gubernator's sequential algorithms (`algorithms.go` tokenBucket and
leakyBucket, with the expiry rule of `cache.go`), cut to what the benchmark's
traffic uses (no RESET_REMAINING) and importing nothing of the program; the
calendar intervals of `DURATION_IS_GREGORIAN` are `gregorian.py`'s, written from
`interval.go`.  `reference.py`'s closed forms are checked against it in
`selfcheck.py`; a later PR may change `tests/`, not this.
"""

from __future__ import annotations

from dataclasses import dataclass

from . import gregorian

TOKEN, LEAKY = 0, 1
UNDER_LIMIT, OVER_LIMIT = 0, 1


@dataclass
class Answer:
    status: int
    limit: int
    remaining: int
    reset_time: int


@dataclass
class _Token:
    limit: int
    duration: int
    remaining: int
    created_at: int
    expire_at: int
    status: int = UNDER_LIMIT


@dataclass
class _Leaky:
    limit: int
    duration: int
    remaining: float
    updated_at: int
    expire_at: int


class Oracle:
    """One daemon's buckets, applied one request at a time."""

    def __init__(self):
        self.items: dict = {}

    def _get(self, key: str, now: int):
        item = self.items.get(key)
        if item is not None and item.expire_at < now:  # strict expiry is a miss
            del self.items[key]
            return None
        return item

    def apply(self, key: str, algorithm: int, hits: int, limit: int, duration: int,
              now: int, behavior: int = 0) -> Answer:
        """`behavior` 4 (`DURATION_IS_GREGORIAN`): `duration` is a calendar
        interval number, not milliseconds."""
        calendar = bool(behavior & gregorian.GREGORIAN)
        if algorithm == LEAKY:
            return self._leaky(key, hits, limit, duration, now, calendar)
        return self._token(key, hits, limit, duration, now, calendar)

    def _token(self, key, hits, limit, duration, now, calendar) -> Answer:
        t = self._get(key, now)
        if t is not None and not isinstance(t, _Token):
            del self.items[key]
            t = None
        if t is None:
            expire = int(gregorian.expiry_ms(now, duration)) if calendar else now + duration
            t = _Token(limit, duration, limit - hits, now, expire)
            rl = Answer(UNDER_LIMIT, limit, t.remaining, t.expire_at)
            if hits > limit:
                rl.status, rl.remaining, t.remaining = OVER_LIMIT, limit, limit
            self.items[key] = t
            return rl
        if t.limit != limit:
            t.remaining = max(t.remaining + limit - t.limit, 0)
            t.limit = limit
        rl = Answer(t.status, limit, t.remaining, t.expire_at)
        if t.duration != duration:
            expire = int(gregorian.expiry_ms(now, duration)) if calendar else t.created_at + duration
            if expire < now:
                del self.items[key]
                return self._token(key, hits, limit, duration, now, calendar)
            t.expire_at = rl.reset_time = expire
        if hits == 0:
            return rl
        if rl.remaining == 0:
            rl.status = t.status = OVER_LIMIT
            return rl
        if t.remaining == hits:
            t.remaining = rl.remaining = 0
            return rl
        if hits > t.remaining:
            rl.status = OVER_LIMIT
            return rl
        t.remaining -= hits
        rl.remaining = t.remaining
        return rl

    def _leaky(self, key, hits, limit, duration, now, calendar) -> Answer:
        b = self._get(key, now)
        if b is not None and not isinstance(b, _Leaky):
            del self.items[key]
            b = None
        # A calendar bucket lives to its interval's end and leaks its limit in
        # upstream's GregorianDuration of the interval (gregorian.interval_ms).
        lasts = int(gregorian.expiry_ms(now, duration)) - now if calendar else duration
        if b is None:
            b = _Leaky(limit, lasts, float(limit - hits), now, now + lasts)
            rl = Answer(UNDER_LIMIT, limit, limit - hits, now + lasts // max(limit, 1))
            if hits > limit:
                rl.status, rl.remaining, b.remaining = OVER_LIMIT, 0, 0.0
            self.items[key] = b
            return rl
        b.limit, b.duration = limit, duration
        rate = float(gregorian.interval_ms(now, duration) if calendar else duration) / float(limit)
        leak = float(now - b.updated_at) / rate
        if int(leak) > 0:
            b.remaining += leak
            b.updated_at = now
        if int(b.remaining) > b.limit:
            b.remaining = float(b.limit)
        rl = Answer(UNDER_LIMIT, b.limit, int(b.remaining), now + int(rate))
        if int(b.remaining) == 0:
            rl.status = OVER_LIMIT
            return rl
        if int(b.remaining) == hits:
            b.remaining -= float(hits)
            rl.remaining = 0
            return rl
        if hits > int(b.remaining):
            rl.status = OVER_LIMIT
            return rl
        if hits == 0:
            return rl
        b.remaining -= float(hits)
        rl.remaining = int(b.remaining)
        b.expire_at = now + lasts
        return rl
