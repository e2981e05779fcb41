"""Upstream Gubernator's calendar intervals (`Behavior.DURATION_IS_GREGORIAN`,
`interval.go:72-146`), for the reference: with the bit set a request's
`duration` is not milliseconds but an interval number, and its bucket lasts to
the end of the calendar interval that contains the request.  Written from
`interval.go` with the standard library; nothing here imports the program.

The calendar is UTC's, whatever the host's zone: the program resolves every
boundary in UTC (`models/shard.py`, `datetime.fromtimestamp(..., tz=utc)`), and
so does this, on its own.

Two values an interval has, and they are not the same thing:

- `expiry_ms`: upstream's `GregorianExpiration` (`interval.go:115-146`) is the
  start of the NEXT interval less one nanosecond, in milliseconds: the
  interval's LAST millisecond, `boundary_ms - 1`.  A bucket expires when
  `expire_at < now`, so it is still the old one at `boundary_ms - 1` and a new
  one at `boundary_ms`.
- `interval_ms`: upstream's `GregorianDuration` (`interval.go:82-107`), which
  sets a calendar LEAKY bucket's leak rate.  Minutes, hours and days are their
  lengths in milliseconds.  For months and years upstream computes
  `end.UnixNano() - begin.UnixNano()/1000000` (`interval.go:97,103`): the end's
  epoch NANOSECONDS less the beginning's epoch MILLISECONDS, some 1.8e18 where
  a month's length is 2.4-2.7e9.  That is upstream's observable behaviour (a
  monthly leaky bucket leaks next to nothing), the program reproduces it, and
  the reference holds the program to THAT value, not to the month's length.
"""

from __future__ import annotations

import datetime as dt

import numpy as np

GREGORIAN = 4  # Behavior.DURATION_IS_GREGORIAN (proto/gubernator.proto)
# Interval numbers (interval.go:72-79).  Weeks (3) upstream refuses.
UNITS = {"minutes": 0, "hours": 1, "days": 2, "months": 4, "years": 5}
DAY_MS = 86_400_000
FIXED_MS = {0: 60_000, 1: 3_600_000, 2: DAY_MS}
_EPOCH = dt.datetime(1970, 1, 1, tzinfo=dt.timezone.utc)


def _bounds_s(day: int, unit: int) -> "tuple[int, int]":
    """Epoch seconds at which the month or year that holds the UTC day number
    `day` begins, and at which the next one does."""
    at = _EPOCH + dt.timedelta(days=day)
    if unit == UNITS["months"]:
        begin = at.replace(day=1)
        end = begin.replace(year=begin.year + begin.month // 12, month=begin.month % 12 + 1)
    elif unit == UNITS["years"]:
        begin = at.replace(month=1, day=1)
        end = begin.replace(year=begin.year + 1)
    else:
        raise ValueError(f"{unit} is not a calendar interval number the reference knows")
    return int((begin - _EPOCH).total_seconds()), int((end - _EPOCH).total_seconds())


def _per_lane(now_ms, unit, fixed, calendar) -> np.ndarray:
    now, unit = np.broadcast_arrays(np.asarray(now_ms).astype(np.int64), np.asarray(unit, np.int64))
    out = np.empty(now.shape, np.int64)
    for u in np.unique(unit).tolist():
        lanes = unit == u
        if u in FIXED_MS:
            out[lanes] = fixed(now[lanes], FIXED_MS[u])
        else:  # a handful of distinct days in a run: the calendar is asked once a day
            days, which = np.unique(now[lanes] // DAY_MS, return_inverse=True)
            out[lanes] = np.array([calendar(*_bounds_s(d, u)) for d in days.tolist()], np.int64)[which]
    return out


def boundary_ms(now_ms, unit) -> np.ndarray:
    """The first millisecond of the interval after the one that holds `now_ms`."""
    return _per_lane(now_ms, unit, lambda t, c: (t // c + 1) * c, lambda begin, end: end * 1000)


def expiry_ms(now_ms, unit) -> np.ndarray:
    """`GregorianExpiration`: the last millisecond of the interval that holds `now_ms`."""
    return boundary_ms(now_ms, unit) - 1


def interval_ms(now_ms, unit) -> np.ndarray:
    """`GregorianDuration`, with upstream's months and years (see above)."""
    return _per_lane(now_ms, unit, lambda t, c: np.full(t.shape, c, np.int64),
                     lambda begin, end: end * 1_000_000_000 - 1 - begin * 1000)
