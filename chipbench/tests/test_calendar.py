"""Calendar quotas in the harness (PR 38), on the CPU in seconds: the calendar
arithmetic against dates worked out by hand, the reference's closed forms
against `oracle.py`'s sequential model on seeded streams (one of them loaded in
the last second before a boundary), the rule that keeps a run clear of a
boundary under a given clock, and `selfcheck.py`'s exit status."""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import gregorian, harness, reference, selfcheck  # noqa: E402
from chipbench.daemon import BenchFailure  # noqa: E402
from chipbench.population import Population  # noqa: E402

DAYS, MONTHS, YEARS = (gregorian.UNITS[u] for u in ("days", "months", "years"))
MAR_2024 = 1_709_251_200_000  # 2024-03-01T00:00:00Z: 1,704,067,200 (2024-01-01) + 31 + 29 days
FEB_2024 = MAR_2024 - 29 * gregorian.DAY_MS
NOV_15_2023 = 1_700_006_400_000  # the first midnight after selfcheck's T0_MS
DEC_2023 = 1_701_388_800_000


def test_an_interval_ends_on_its_last_millisecond():
    assert gregorian.expiry_ms(MAR_2024 - 1, MONTHS) == MAR_2024 - 1  # February's last instant is February's
    assert gregorian.expiry_ms(FEB_2024, MONTHS) == MAR_2024 - 1  # and its first: a leap year's 29 days
    assert gregorian.expiry_ms(MAR_2024, MONTHS) == MAR_2024 + 31 * gregorian.DAY_MS - 1
    assert gregorian.expiry_ms(MAR_2024 - 1, DAYS) == MAR_2024 - 1
    assert gregorian.expiry_ms(MAR_2024, DAYS) == MAR_2024 + gregorian.DAY_MS - 1
    assert gregorian.expiry_ms(DEC_2023 + 5, YEARS) == 1_704_067_200_000 - 1  # December rolls the year
    assert gregorian.expiry_ms(DEC_2023 + 30 * gregorian.DAY_MS + 5, MONTHS) == 1_704_067_200_000 - 1
    lanes = gregorian.expiry_ms(np.array([MAR_2024 - 1, MAR_2024, MAR_2024]), np.array([MONTHS, MONTHS, DAYS]))
    assert lanes.tolist() == [MAR_2024 - 1, MAR_2024 + 31 * gregorian.DAY_MS - 1, MAR_2024 + gregorian.DAY_MS - 1]


def test_a_month_leaks_by_upstream_s_nanoseconds_less_milliseconds():
    assert gregorian.interval_ms(FEB_2024 + 12345, DAYS) == 86_400_000
    # interval.go:97: end.UnixNano() - begin.UnixNano()/1000000, end = 2024-03-01 less a nanosecond
    assert gregorian.interval_ms(FEB_2024 + 12345, MONTHS) == MAR_2024 * 1_000_000 - 1 - FEB_2024
    assert gregorian.interval_ms(FEB_2024, YEARS) == 1_735_689_600 * 10**9 - 1 - 1_704_067_200_000


def stream(spec, seed, **how):
    pop = Population(spec, 200, seed)
    compared, span_ms = selfcheck.drive_oracle(pop, np.random.default_rng(seed), **how)
    return [c.line() for c in compared if not c.ok], span_ms


ROOMY = dict(selfcheck.CALENDAR_SPEC, **selfcheck.ROOMY)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("spec", [selfcheck.CALENDAR_SPEC, ROOMY], ids=["tight", "roomy"])
def test_the_reference_passes_the_oracle_s_calendar_stream(spec, seed):
    assert stream(spec, seed)[0] == []


@pytest.mark.parametrize("boundary", [NOV_15_2023, DEC_2023], ids=["midnight", "month"])
def test_keys_loaded_in_the_last_second_before_a_boundary(boundary):
    """The whole stream in under a second, ending 10 ms before the boundary:
    every key's interval ends on the next millisecond but nine, and the
    reference agrees with the oracle; the same stream begun half its length
    later crosses the boundary, buckets reset inside it, and it does not pass."""
    _, span_ms = stream(selfcheck.CALENDAR_SPEC, 3, pace=0.005)
    assert span_ms < 1000
    failing, again = stream(selfcheck.CALENDAR_SPEC, 3, pace=0.005, start_ms=boundary - span_ms - 10)
    assert again == span_ms and failing == []
    failing, _ = stream(selfcheck.CALENDAR_SPEC, 3, pace=0.005, start_ms=boundary - span_ms // 2)
    assert any("accounting.token_keys_wrong" in row for row in failing), failing


def test_a_load_in_flight_at_midnight_may_land_on_either_day_and_nowhere_else():
    spec = dict(selfcheck.CALENDAR_SPEC, calendar={"share": 1.0, "units": {"days": 1.0}, "horizon_s": 300},
                leaky_share=0.0)
    pop = Population(spec, 4, 1)
    sample = np.arange(4)
    lim = pop.limit[sample]
    lo = np.full(4, NOV_15_2023 - 3.0)
    hi = np.full(4, NOV_15_2023 + 2.0)
    reset = np.array([NOV_15_2023 - 1, NOV_15_2023 + gregorian.DAY_MS - 1,  # the old day's end, the new day's
                      NOV_15_2023, NOV_15_2023 - 3 + pop.duration_ms])  # neither; creation + an hour
    a = reference.Answers(*(np.zeros(0, np.int64) for _ in range(6)))
    compared = reference.readback(pop, np.zeros(4, np.int64), a, sample, lo, hi, hi + 10, hi + 20,
                                  np.zeros(4, np.int64), lim, lim - 1, reset)
    born = next(c for c in compared if c.name == "readback.token_keys_born_outside_load")
    assert born.value == 2 and all(c.ok for c in compared if c is not born)


class Clock:
    def __init__(self, now_ms):
        self.now_s, self.slept = now_ms / 1e3, []

    def __call__(self):
        return self.now_s

    def sleep(self, s):
        self.slept.append(s)
        self.now_s += s


def population(units, horizon_s=300):
    return Population(dict(selfcheck.POP_SPEC, calendar={"share": 0.5, "units": units, "horizon_s": horizon_s}), 50, 1)


def test_a_run_waits_past_a_boundary_that_lies_inside_its_horizon():
    days_and_months = population({"days": 0.5, "months": 0.5})
    clock = Clock(NOV_15_2023 - 120_000)  # two minutes to midnight
    assert harness.wait_past_boundary(days_and_months, clock, clock.sleep) == 120 + harness.BOUNDARY_MARGIN_S
    assert clock.slept == [122.0] and clock() * 1e3 > NOV_15_2023
    assert not harness.boundary_crossed(days_and_months, clock() * 1e3, clock() * 1e3 + 300_000)
    clock = Clock(NOV_15_2023 - 301_000)  # outside the horizon: the run starts at once and ends before it
    assert harness.wait_past_boundary(days_and_months, clock, clock.sleep) == 0.0 and clock.slept == []
    clock = Clock(NOV_15_2023 - 120_000)  # a population of monthly quotas alone passes a midnight that ends no month
    assert harness.wait_past_boundary(population({"months": 1.0}), clock, clock.sleep) == 0.0
    clock = Clock(DEC_2023 - 120_000)
    assert harness.wait_past_boundary(population({"months": 1.0}), clock, clock.sleep) == 122.0
    clock = Clock(NOV_15_2023 - 120_000)  # no calendar quota: no clock is read
    assert harness.wait_past_boundary(Population(selfcheck.POP_SPEC, 50, 1), None, None) == 0.0


def test_a_run_that_held_a_boundary_has_no_verdict_and_minutes_fit_no_run():
    pop = population({"days": 1.0})
    assert harness.boundary_crossed(pop, NOV_15_2023 - 5_000, NOV_15_2023)
    assert not harness.boundary_crossed(pop, NOV_15_2023 - 5_000, NOV_15_2023 - 1)
    assert not harness.boundary_crossed(Population(selfcheck.POP_SPEC, 50, 1), 0, 4e12)
    clock = Clock(NOV_15_2023 - 20_000)
    with pytest.raises(BenchFailure, match="no run fits"):
        harness.wait_past_boundary(population({"minutes": 1.0}), clock, clock.sleep)


def test_selfcheck_exits_0():
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chipbench", "selfcheck.py")],
                          capture_output=True, text=True, cwd=REPO, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0 and proc.stdout.strip().endswith("all hold"), proc.stdout[-3000:]
    for fault in ("the bit ignored", "an hour's rate", "daily and monthly quotas passes"):
        assert any(row.startswith("ok") and fault in row for row in proc.stdout.splitlines()), fault
