"""The resident population of a deployment and the skew of the keys asked for,
both made from the seed.  What a configuration file says under `population`
decides everything here; nothing is particular to one cell."""

from __future__ import annotations

import numpy as np

from . import gregorian

_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)


class Population:
    """`n` distinct keys.  A key is its index in decimal, zero-filled, then
    `k` and eight hex digits of salt: the index leads because FNV clusters
    keys that differ only in their tail, and every key has the same width so
    that a frame's key column is one fancy-index away.

    Limits come in `limit_tiers` values spaced evenly in the logarithm between
    `limit_min` and `limit_max`: a deployment sets limits per plan, not per
    user, and the program's dictionary wire holds 256 distinct
    (algorithm, hits, limit, duration) rows a dispatch.

    Every key also has a `behavior` and a `duration`, which a frame carries a
    lane at a time: 0 and the configuration's `duration_ms`, unless the
    configuration has `calendar`: `{"share": s, "units": {"days": a, "months":
    b}, "horizon_s": h}` makes a share `s` of the keys calendar quotas
    (`DURATION_IS_GREGORIAN`, duration = upstream's interval number), their
    units in the given shares; `horizon_s` is the longest that a run's load,
    ramp, window and read-back take together (`harness.wait_past_boundary`).
    The calendar's draws come from a stream of their own, so a configuration
    without it keeps every other array bit for bit."""

    def __init__(self, spec: dict, n: int, seed: int):
        self.n = n
        self.name = spec["name"]
        self.duration_ms = int(spec["duration_ms"])
        rng = np.random.default_rng([seed, 0x706F70])
        digits = max(7, len(str(n - 1)))
        self.key_width = digits + 9
        idx = np.arange(n, dtype=np.int64)
        salt = rng.integers(0, 1 << 32, size=n, dtype=np.int64)
        kb = np.empty((n, self.key_width), np.uint8)
        for d in range(digits):
            kb[:, digits - 1 - d] = 48 + (idx // 10**d) % 10
        kb[:, digits] = ord("k")
        for h in range(8):
            kb[:, digits + 8 - h] = _HEX[(salt >> (4 * h)) & 15]
        self.key_bytes = kb
        self.algo = (rng.random(n) < float(spec["leaky_share"])).astype(np.int32)
        tiers = np.unique(np.round(np.geomspace(
            spec["limit_min"], spec["limit_max"], int(spec["limit_tiers"])
        )).astype(np.int64))
        self.limit = tiers[rng.integers(0, len(tiers), size=n)]
        # Scrambled Zipfian (YCSB): rank r is asked for with weight r**-theta,
        # and which key holds which rank is a seeded permutation.
        theta = float(spec["zipf_theta"])
        weights = np.arange(1, n + 1, dtype=np.float64) ** -theta
        self._cdf = np.cumsum(weights)
        self._cdf /= self._cdf[-1]
        self.key_of_rank = rng.permutation(n).astype(np.int32)
        self.behavior = np.zeros(n, np.int32)
        self.duration = np.full(n, self.duration_ms, np.int64)
        self.calendar_units: list = []  # the interval numbers some key holds
        self.calendar_horizon_s = 0.0
        calendar = spec.get("calendar")
        if calendar:
            rng = np.random.default_rng([seed, 0x63616C])
            names = sorted(calendar["units"])
            shares = np.array([calendar["units"][u] for u in names], np.float64)
            unit = rng.choice([gregorian.UNITS[u] for u in names], size=n, p=shares / shares.sum())
            quota = rng.random(n) < float(calendar["share"])
            self.behavior[quota] = gregorian.GREGORIAN
            self.duration[quota] = unit[quota]
            self.calendar_units = np.unique(unit[quota]).tolist()
            self.calendar_horizon_s = float(calendar["horizon_s"])

    def draw(self, rng, size: int) -> np.ndarray:
        """`size` key indices, Zipfian over the resident set."""
        ranks = np.searchsorted(self._cdf, rng.random(size), side="right")
        return self.key_of_rank[np.minimum(ranks, self.n - 1)]

    def unique_key(self, i: int) -> str:
        return self.key_bytes[i].tobytes().decode()

    def keys_blob(self, idx) -> bytes:
        return self.key_bytes[idx].tobytes()
