"""What decides `correct`: the order-free accounting of every answer since the
load, and the read-back after the window, both in closed form over numpy
counters (checked against `oracle.py`'s sequential model in `selfcheck.py`).
Nothing here imports the program.

The order between connections is unknown, so nothing depends on it.  Every
check takes one hit, every bucket lasts longer than the run, and the load left
each bucket at `limit - 1`.  For a TOKEN bucket asked n more times, whatever
the order: min(n, limit - 1) answers are UNDER_LIMIT, their `remaining` are
limit-2, limit-3, ... each exactly once, and the other answers are OVER_LIMIT
with `remaining` 0.  One lost or doubled hit changes the count or the sum.

A LEAKY bucket leaks back during the run, and the daemon stamps a request at an
instant the client only brackets ([sent, received], on the shared wall clock).
With a_i the instants of the admitted hits, X(t) = (t - t_load)/rate - #{a_i <=
t} and M(t) = sup X over [t_load, t], the level is limit + X(t) - max(1, M(t))
(a reservoir with a ceiling at `limit` that started one below it).  Moving an
admitted hit later, or the load later, or the reading earlier, can only lower
the level read; so the latest instants of the hits with the earliest of the
reading bound it below, and the reverse above.

Calendar quotas (`population.calendar`: `DURATION_IS_GREGORIAN`, a duration of
2 = days or 4 = months; `gregorian.py`) change two things and nothing else,
because no run holds a calendar boundary (`harness.wait_past_boundary`), so no
bucket resets inside one.  (1) A calendar TOKEN bucket's `reset_time` is not
its creation plus a duration but, exactly, the last millisecond of the UTC
calendar interval that holds its load: upstream's `GregorianExpiration`,
`boundary_ms - 1`.  (2) A calendar LEAKY bucket leaks `limit` tokens in
upstream's `GregorianDuration` of that interval: 86,400,000 ms for a day, and
for a month upstream's nanoseconds-less-milliseconds (`interval.go:97`, some
1.8e18 ms: next to nothing leaks in a run), which the program reproduces and
the reference holds it to; a plain key leaks them in `duration_ms`.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import gregorian

TOKEN, LEAKY = 0, 1
UNDER, OVER = 0, 1


@dataclass
class Compared:
    """One number the run compares, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit

    def line(self) -> str:
        return f"{self.name} {self.value:g} (limit {self.limit:g}) {'ok' if self.ok else 'WRONG'}"


@dataclass
class Answers:
    """Every check answered since the load, flattened: one entry a check."""
    key: np.ndarray  # int32 index into the population
    status: np.ndarray
    limit: np.ndarray
    remaining: np.ndarray
    sent_ms: np.ndarray  # wall clock: the request left after this
    recv_ms: np.ndarray  # and its answer was in before this


def check_first_hits(pop, idx, hits, status, limit, remaining) -> int:
    """Wrong answers of one load frame: a fresh bucket that took `hits`."""
    return int((
        (status != UNDER) | (limit != pop.limit[idx]) | (remaining != pop.limit[idx] - hits)
    ).sum())


def token_accounting(pop, a: Answers) -> "tuple[list, np.ndarray]":
    """(numbers compared, hits asked per key since the load)."""
    n = pop.n
    asked = np.bincount(a.key, minlength=n)
    under = a.status == UNDER
    n_under = np.bincount(a.key[under], minlength=n)
    sum_under = np.bincount(a.key[under], weights=a.remaining[under].astype(np.float64),
                            minlength=n)
    token = pop.algo == TOKEN
    lim = pop.limit.astype(np.float64)
    u = np.minimum(asked, pop.limit - 1).astype(np.float64)
    want_sum = u * (lim - 1.0) - u * (u + 1.0) / 2.0
    return [
        Compared("accounting.status_not_0_or_1", int(((a.status != UNDER) & (a.status != OVER)).sum()), 0),
        Compared("accounting.limit_not_echoed", int((a.limit != pop.limit[a.key]).sum()), 0),
        Compared("accounting.over_limit_with_remaining", int((a.remaining[~under] != 0).sum()), 0),
        Compared("accounting.token_keys_wrong_under_count", int((token & (n_under != u)).sum()), 0),
        Compared("accounting.token_keys_wrong_remaining_sum", int((token & (sum_under != want_sum)).sum()), 0),
    ], asked


def leak_duration_ms(pop, keys, at_ms) -> np.ndarray:
    """Milliseconds in which each of `keys` leaks its whole `limit`: the
    configuration's `duration_ms`, or for a calendar quota upstream's
    `GregorianDuration` of the interval that holds `at_ms` (its load)."""
    out = np.full(len(keys), float(pop.duration_ms))
    quota = pop.behavior[keys] == gregorian.GREGORIAN
    if quota.any():
        out[quota] = gregorian.interval_ms(at_ms[quota], pop.duration[keys][quota])
    return out


def leaky_levels(limit, duration_ms, load_lo, load_hi, read_lo, read_hi, key_slot,
                 hit_lo, hit_hi) -> "tuple[np.ndarray, np.ndarray]":
    """Bounds on the level (in tokens, not yet floored) of K leaky buckets at
    their read-back.  `limit`, `duration_ms`, `load_*`, `read_*` have one entry
    a bucket: what it leaks its limit in, and the bracket of the load's and of
    the reading's instant.  `key_slot`, `hit_lo`, `hit_hi` have one entry an
    ADMITTED hit: which bucket, and its bracket."""
    limit = limit.astype(np.float64)
    rate = duration_ms / limit  # ms a token

    def level(t_load, t_read, t_hit):
        order = np.lexsort((t_hit, key_slot))
        k, t = key_slot[order], t_hit[order]
        admitted = np.bincount(k, minlength=len(limit)).astype(np.float64)
        x_end = (t_read - t_load) / rate - admitted
        m = np.maximum(x_end, 0.0)
        if len(k):
            starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
            rank = np.arange(len(k)) - np.repeat(starts, np.diff(np.r_[starts, len(k)]))
            before_hit = (t - t_load[k]) / rate[k] - rank  # X just before each hit
            np.maximum.at(m, k[starts], np.maximum.reduceat(before_hit, starts))
        return limit + x_end - np.maximum(1.0, m)

    low = level(load_hi, read_lo, hit_hi)
    high = level(load_lo, read_hi, hit_lo)
    return low, high


def readback(pop, asked, a: Answers, sample, load_lo, load_hi, read_lo, read_hi,
             status, limit, remaining, reset_time) -> list:
    """The sampled keys, read with hits=0 after the window.  `load_*`/`read_*`
    bracket each sampled key's load and reading on the wall clock."""
    lim = pop.limit[sample]
    token = pop.algo[sample] == TOKEN
    u = np.minimum(asked[sample], lim - 1)
    want_status = np.where(asked[sample] > lim - 1, OVER, UNDER)
    token_wrong = token & ((remaining != lim - 1 - u) | (status != want_status))
    # A token bucket's reset time is its creation plus the duration: one that
    # was evicted and made again would show a later one.  A calendar quota's
    # is the last millisecond of the interval that holds its creation, exactly:
    # of the one or (a load in flight at a boundary) two that its bracket meets.
    quota = pop.behavior[sample] == gregorian.GREGORIAN
    ends = [gregorian.expiry_ms(t[quota], pop.duration[sample][quota]) for t in (load_lo, load_hi)]
    born_wrong = token & (
        (reset_time < load_lo + pop.duration_ms) | (reset_time > load_hi + pop.duration_ms)
    )
    born_wrong[quota] = token[quota] & (reset_time[quota] != ends[0]) & (reset_time[quota] != ends[1])
    slot_of = np.full(pop.n, -1, np.int64)
    leaky_keys = sample[~token]
    slot_of[leaky_keys] = np.arange(len(leaky_keys))
    hit = (a.status == UNDER) & (slot_of[a.key] >= 0)
    low, high = leaky_levels(
        lim[~token], leak_duration_ms(pop, leaky_keys, load_lo[~token]),
        load_lo[~token], load_hi[~token],
        read_lo[~token], read_hi[~token], slot_of[a.key[hit]],
        a.sent_ms[hit].astype(np.float64), a.recv_ms[hit].astype(np.float64),
    )
    got = remaining[~token].astype(np.float64)
    top = lim[~token].astype(np.float64)
    excess = np.maximum(
        np.floor(np.clip(low, 0.0, top)) - got, got - np.floor(np.clip(high, 0.0, top))
    )
    return [
        Compared("readback.limit_not_echoed", int((limit != lim).sum()), 0),
        Compared("readback.token_keys_wrong", int(token_wrong.sum()), 0),
        Compared("readback.token_keys_born_outside_load", int(born_wrong.sum()), 0),
        Compared("readback.leaky_tokens_outside_bracket",
                 float(excess.max()) if len(excess) else 0.0, LEAKY_SLACK_TOKENS),
    ]


# A leaky answer may sit this many whole tokens outside the bracket of the
# continuous model: the daemon credits a leak only once a whole token has
# leaked (up to one token pending), keeps the level in fixed point, and reports
# its floor.  Set from readings on the chip: PERF.md section 2.
LEAKY_SLACK_TOKENS = 2.0


def leaky_admissions(pop, a: Answers, load_lo_all, t_end_ms: float) -> Compared:
    """No leaky bucket admitted more than it held plus what leaked back over
    the run (every key, not only the sampled ones)."""
    leaky = pop.algo == LEAKY
    admitted = np.bincount(a.key[a.status == UNDER], minlength=pop.n).astype(np.float64)
    lim = pop.limit.astype(np.float64)
    duration = leak_duration_ms(pop, np.arange(pop.n), load_lo_all)
    room = (lim - 1.0) + (t_end_ms - load_lo_all) * lim / duration + 1.0
    over = np.where(leaky, admitted - room, -np.inf)
    return Compared("accounting.leaky_admitted_beyond_leak", float(max(over.max(), 0.0)), 0.0)
