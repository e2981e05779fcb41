"""The cell `greg-10m.frames` at test size, on the CPU: 8,000 keys of
`chipbench/population.py` with the configuration's own `population` block
(every key a calendar quota, a UTC day or a UTC month, token and leaky) in
8,192 slots, loaded, asked for and read back in 512-lane frames drawn by its
scrambled Zipfian 0.99.  Every lane carries DURATION_IS_GREGORIAN, and every
frame holds a monthly lane, so every dispatch takes the wide (i64) answer of
the dictionary wire.

Held here: a calendar frame stays on a served daemon's native ingress lane
(native `frames` grow, `fallbacks` stay 0) and every lane of load, traffic
and read-back equals the sequential oracle (status, remaining, `reset_time`
on the interval's last millisecond, a monthly leaky bucket's upstream rate);
the same on a mesh of S = 1, 2 and 4; a frame that mixes calendar and plain
lanes; a frame with a weeks lane or a duration of 6 falls back whole and
answers upstream's error lane by lane; the boundaries of the calendar under a
frozen clock; the vectorised resolve equals `GregResolver`; after warm-up a
monthly frame compiles nothing; the counters and the phase; the cell's files
and the readers of its four metrics.  Everything is made from SEED."""

from __future__ import annotations

import calendar as _calendar
import datetime as _dt
import functools
import json
import os
import sys

import jax
import numpy as np
import pytest

from gubernator_tpu import native, saturation, telemetry, wire
from gubernator_tpu.models.shard import GregResolver, greg_lanes, resolve_greg_columns
from gubernator_tpu.parallel.mesh import MeshBucketStore
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest
from gubernator_tpu.utils import gregorian

from . import oracle as orc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.daemon import Http, metric_sum  # noqa: E402
from chipbench.generators import frames as gen_frames  # noqa: E402
from chipbench import gubc  # noqa: E402
from chipbench.population import Population  # noqa: E402
from chipbench.readers import counter_share, mesh_tally, phase_ms_per  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the columnar path needs the native host runtime")

SEED = 39
KEYS = 8_000
SLOTS = 8_192
LANES = 512
NAME = "bench"
GREG = int(Behavior.DURATION_IS_GREGORIAN)
DAYS, WEEKS, MONTHS = gregorian.GREGORIAN_DAYS, gregorian.GREGORIAN_WEEKS, gregorian.GREGORIAN_MONTHS
I32_MAX = (1 << 31) - 1
RATE_SLACK_MS = 2


def _ms(*ymdhms, ms: int = 0) -> int:
    return _calendar.timegm(_dt.datetime(*ymdhms).timetuple()) * 1000 + ms


T0 = _ms(2026, 9, 21, 13, 46, 40)
SWEEP = [
    _ms(2026, 9, 21, 13, 46, 40, ms=123), _ms(2026, 3, 14, 23, 59, 59, ms=999), _ms(2026, 3, 15),
    _ms(2026, 1, 1), _ms(2026, 1, 31, 23, 59, 59, ms=999), _ms(2026, 2, 28, 23, 59, 59, ms=999),
    _ms(2028, 2, 29, 12), _ms(2026, 4, 30, 23, 59, 59, ms=999), _ms(2026, 12, 31, 23, 59, 59, ms=999),
    _ms(2027, 1, 1), _ms(1970, 1, 1), _ms(2038, 1, 19, 3, 14, 8),
]
TRAFFIC_FRAMES = 12
DRAIN_FRAMES = 2  # Zipfian frames of DRAIN_HITS a lane: buckets run dry
DRAIN_HITS = 60_000
READBACK_FRAMES = 3
CELL = "greg-10m.frames"
BYPASS = "ycsb-f-32m.frames"
SHARDS = [1, 2, 4]
LABEL_WIDE = "mesh:dispatch:solo:wide"
LABEL_NARROW = "mesh:dispatch:solo:narrow"
NEW_METRICS = ("calendar.lane_share", "calendar.resolve_ms_per_dispatch", "wire.wide_share",
               "ingress.native_frame_share")
NATIVE_INGRESS = "gubernator_native_ingress_batches_total"


def _cell_json(*parts):
    with open(os.path.join(REPO, "chipbench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pop():
    assert _cell_json("traffic", "frames-pool4k.json")["lanes_per_request"] == 4096  # the cell's; 512 here
    pop = Population(_cell_json("configs", "greg-10m.json")["population"], KEYS, SEED)
    assert (pop.behavior == GREG).all() and set(pop.duration.tolist()) == {DAYS, MONTHS}
    assert set(pop.algo.tolist()) == {0, 1}
    return pop


def _takes(pop):
    """[(key indices, hits, now_ms)]: the load (every key once, one hit, the
    tail frame filled with hits=0 re-reads of loaded token keys, as the
    harness fills it), the traffic (Zipfian frames, one hit a lane, seconds
    apart so that leaky buckets leak; then two frames of 60,000 hits a lane,
    so that some buckets run dry), the read-back (hits=0)."""
    rng = np.random.default_rng([SEED, 0x63616C])
    fill = np.flatnonzero(pop.algo[: 4 * LANES] == 0)[:LANES]
    out, now = [], T0
    for lo in range(0, pop.n, LANES):
        hi = min(lo + LANES, pop.n)
        idx = np.concatenate([np.arange(lo, hi), fill[: LANES - (hi - lo)]])
        hits = np.concatenate([np.ones(hi - lo, np.int64), np.zeros(LANES - (hi - lo), np.int64)])
        out.append((idx, hits, now))
        now += int(rng.integers(1, 20))
    for t in range(TRAFFIC_FRAMES + DRAIN_FRAMES):
        now += int(rng.integers(0, 4000))
        hits = 1 if t < TRAFFIC_FRAMES else DRAIN_HITS
        out.append((pop.draw(rng, LANES), np.full(LANES, hits, np.int64), now))
    for _ in range(READBACK_FRAMES):
        now += int(rng.integers(1, 20))
        out.append((pop.draw(rng, LANES), np.zeros(LANES, np.int64), now))
    return out


def _oracle_rows(cache, keys, algo, behavior, hits, limit, duration, now) -> np.ndarray:
    """What upstream's sequential algorithm answers a frame, lane by lane.

    One number is taken in the kernel's arithmetic, not the oracle's: the
    `reset_time` of a monthly or yearly LEAKY bucket that exists, `now +
    int64(rate)`.  Upstream divides in float64, and its duration of such an
    interval (nanoseconds less milliseconds, ~1.8e18) passes 2**53, so the
    float form carries a rounding to a multiple of 256 where the kernel
    divides the integers exactly: they lie within RATE_SLACK_MS of each other,
    half a million years ahead (test_the_kernels_integer_rate_...)."""
    rows = np.empty((len(keys), 4), np.int64)
    at = _dt.datetime.fromtimestamp(now / 1000.0, tz=_dt.timezone.utc)
    for lane, key in enumerate(keys):
        r = orc.apply(cache, RateLimitRequest(
            name=NAME, unique_key=key, hits=int(hits[lane]), limit=int(limit[lane]),
            duration=int(duration[lane]), algorithm=Algorithm(int(algo[lane])),
            behavior=int(behavior[lane])), now)
        rows[lane] = (int(r.status), r.limit, r.remaining, r.reset_time)
        if algo[lane] == 1 and behavior[lane] & GREG and duration[lane] in (MONTHS, gregorian.GREGORIAN_YEARS):
            length = gregorian.gregorian_duration(at, int(duration[lane]))
            if r.reset_time == now + int(float(length) / float(limit[lane])):
                rows[lane, 3] = now + length // int(limit[lane])
    return rows


@pytest.fixture(scope="module")
def takes(pop):
    return _takes(pop)


@pytest.fixture(scope="module")
def expected(pop, takes):
    cache = orc.OracleCache()
    return [
        _oracle_rows(cache, [pop.unique_key(i) for i in idx.tolist()], pop.algo[idx],
                     pop.behavior[idx], hits, pop.limit[idx], pop.duration[idx], now)
        for idx, hits, now in takes
    ]


def _serve(pop, takes, shards: int, warm: bool = False) -> dict:
    """The takes through a fresh `MeshBucketStore`, resolved as the pump
    resolves them; what it answered and what the process counted."""
    keys = [f"{NAME}_{pop.unique_key(i)}" for i in range(pop.n)]
    telemetry.set_enabled(True)
    telemetry.reset()
    saturation.reset()
    store = MeshBucketStore(
        capacity_per_shard=SLOTS // min(shards, 2), devices=jax.devices()[:shards])
    if warm:
        store.warmup(T0 - 60_000, warm_shapes=[LANES])
        telemetry.mark_steady()
    out = {"store": store, "before": saturation.mesh_tally.snapshot(),
           "runs_before": dict(telemetry.snapshot()["programRuns"]),
           "compiles_before": telemetry.compile_count(),
           "wide_compiles_before": telemetry.compile_snapshot().get(LABEL_WIDE, {"count": 0})["count"]}
    answers = []
    for idx, hits, now in takes:
        expire, length, errors, distinct = resolve_greg_columns(
            greg_lanes(pop.behavior[idx]), pop.duration[idx], now)
        assert errors == [] and distinct == 2
        r = store.apply_columns(
            [keys[i] for i in idx.tolist()], pop.algo[idx], pop.behavior[idx], hits,
            pop.limit[idx], pop.duration[idx], now, expire, length)
        answers.append(np.stack([r["status"], r["limit"], r["remaining"], r["reset_time"]], axis=1))
    out.update(
        answers=answers, after=saturation.mesh_tally.snapshot(),
        runs=telemetry.snapshot()["programRuns"], compiles=telemetry.compile_count(),
        steady_recompiles=telemetry.steady_recompile_count())
    return out


@pytest.fixture(scope="module")
def served(pop, takes):
    """shards -> the cell's run on that many devices (S = 1 warmed up first,
    as the daemon is); each is driven once."""

    @functools.cache
    def run(shards: int):
        return _serve(pop, takes, shards, warm=shards == 1)

    yield run
    telemetry.reset()
    saturation.reset()


def _grown(run, key):
    return run["after"][key] - run["before"][key]


def _runs(run, label) -> int:
    return (run["runs"].get(label, {"count": 0})["count"]
            - run["runs_before"].get(label, {"count": 0})["count"])


def _wrong(answers, expected):
    return [(t, np.flatnonzero((got != want).any(axis=1))[:5].tolist())
            for t, (got, want) in enumerate(zip(answers, expected)) if (got != want).any()]


# ---------------------------------------------------------------------
# The frames are the cell's, and the oracle's answers are a calendar's
# ---------------------------------------------------------------------
def test_every_frame_holds_days_and_months_token_and_leaky(pop, takes, expected):
    load = -(-KEYS // LANES)
    assert len(takes) == load + TRAFFIC_FRAMES + DRAIN_FRAMES + READBACK_FRAMES
    for idx, _, _ in takes:
        assert set(pop.duration[idx].tolist()) == {DAYS, MONTHS}
        assert set(pop.algo[idx].tolist()) == {0, 1}
    day_end, month_end = _ms(2026, 9, 22) - 1, _ms(2026, 10, 1) - 1
    month_length = gregorian.gregorian_duration(
        _dt.datetime.fromtimestamp(T0 / 1000, tz=_dt.timezone.utc), MONTHS)
    assert month_length > 1.7e18  # upstream's nanoseconds less milliseconds, kept
    for t, ((idx, hits, now), want) in enumerate(zip(takes, expected)):
        token = pop.algo[idx] == 0
        end = np.where(pop.duration[idx] == DAYS, day_end, month_end)
        # A token bucket resets on its interval's last millisecond.
        assert (want[token, 3] == end[token]).all()
        if t < load:
            continue
        # A monthly leaky bucket that exists leaks at upstream's rate: a token
        # every month_length / limit milliseconds, far past any i32.
        monthly_leaky = ~token & (pop.duration[idx] == MONTHS)
        rate = month_length // pop.limit[idx]
        assert monthly_leaky.any()
        assert (want[monthly_leaky, 3] == now + rate[monthly_leaky]).all()
        assert (want[monthly_leaky, 3] - now > I32_MAX).all()
    assert sum(int((want[:, 0] == 1).sum()) for want in expected) > 0  # some bucket ran dry


def test_the_kernels_integer_rate_lies_within_two_ms_of_upstreams_float(pop):
    """What `_oracle_rows` waives, and no more: over every month and year of
    SWEEP's instants and every limit of the population."""
    worst = 0
    for now in SWEEP:
        at = _dt.datetime.fromtimestamp(now / 1000.0, tz=_dt.timezone.utc)
        for kind in (MONTHS, gregorian.GREGORIAN_YEARS):
            length = gregorian.gregorian_duration(at, kind)
            for limit in np.unique(pop.limit).tolist():
                worst = max(worst, abs(int(float(length) / float(limit)) - length // limit))
    assert 0 < worst <= RATE_SLACK_MS


@pytest.mark.parametrize("shards", SHARDS)
def test_every_lane_equals_the_sequential_oracle_on_a_mesh(served, expected, shards):
    """Status, limit, remaining and reset of every lane of load, traffic and
    read-back, on one device and on two and four shards."""
    run = served(shards)
    assert _wrong(run["answers"], expected) == []
    assert run["store"].size() >= KEYS
    run["store"].check_consistency()


@pytest.mark.parametrize("shards", SHARDS)
def test_every_dispatch_is_calendar_wide_and_on_the_dictionary(served, takes, shards):
    run = served(shards)
    n = len(takes)
    assert _grown(run, "dispatches") == _grown(run, "wideDispatches") == n
    assert _grown(run, "lanes") == _grown(run, "calendarLanes") == n * LANES
    assert _grown(run, "laneWireDispatches") == 0
    assert _grown(run, "configRows") <= n * 2 * 16 * 2 * 2  # units x tiers x algorithms x hits
    assert _runs(run, LABEL_WIDE) == n and _runs(run, LABEL_NARROW) == 0


def test_after_warm_up_a_monthly_frame_compiles_nothing(served, takes):
    """Warm-up compiles the dictionary wire's wide answer at the warm bucket,
    beside the two narrow legs, by one launch on an all-inert wire after the
    fused launches.  Load, traffic and read-back,
    every frame of them monthly, then compile nothing: the steady-state
    counter (which counts this program since PR 39: it is no longer `lazy`)
    and the count of compiles both stand still."""
    run = served(1)
    assert run["steady_recompiles"] == 0
    assert run["compiles"] == run["compiles_before"]
    assert run["wide_compiles_before"] == 1
    assert run["runs_before"][LABEL_WIDE]["count"] == 1  # the one launch on an all-inert wire
    assert run["runs_before"][LABEL_NARROW]["count"] == 2
    assert LABEL_WIDE in telemetry.snapshot()["startup"]["programs"]
    assert _runs(run, LABEL_WIDE) == len(takes)


def test_a_compile_of_the_warmed_wide_program_counts_and_of_the_lazy_one_does_not():
    """Since warm-up compiles the dictionary wire's solo wide program, one that
    compiles after warm-up is shape churn like any other; the per-lane wire's
    wide answer is still deferred by design."""
    telemetry.set_enabled(True)
    store = MeshBucketStore(capacity_per_shard=1 << 13, devices=jax.devices()[:1])
    telemetry.mark_steady()
    n = 200  # pad bucket 256: no other test of this file launches it

    def counted(label):
        row = telemetry.compile_snapshot().get(label, {"count": 0, "steady_recompiles": 0})
        return row["count"], row["steady_recompiles"]

    try:
        for label, force_wire in ((LABEL_WIDE, None), ("mesh:dispatch:solo:lanes64", "wide")):
            before = counted(label)
            store.apply_columns(
                [f"late{i}" for i in range(n)], np.zeros(n, np.int32), np.zeros(n, np.int32),
                np.ones(n, np.int64), np.full(n, 1 << 31, np.int64), np.full(n, 60_000, np.int64),
                T0, force_wire=force_wire)
            compiled, steady = (after - was for after, was in zip(counted(label), before))
            assert compiled >= 1 and steady == (compiled if label == LABEL_WIDE else 0), label
    finally:
        telemetry.reset()


def test_warm_up_leaves_no_live_bucket_behind():
    store = MeshBucketStore(capacity_per_shard=1024, devices=jax.devices()[:1])
    store.warmup(T0, warm_shapes=[64])
    key = "__warmup__:0"
    got = store.apply_columns(
        [key], np.zeros(1, np.int32), np.zeros(1, np.int32), np.ones(1, np.int64),
        np.full(1, 10, np.int64), np.full(1, 60_000, np.int64), T0 + 2)
    # Warm-up's 1 ms buckets have expired, and its wide launch touched no
    # slot (every lane inert): a fresh bucket of limit 10.
    assert (int(got["status"][0]), int(got["remaining"][0]), int(got["reset_time"][0])) == (
        0, 9, T0 + 2 + 60_000)


# ---------------------------------------------------------------------
# The vectorised resolve is GregResolver's, lane for lane
# ---------------------------------------------------------------------
@pytest.mark.parametrize("now", SWEEP + np.random.default_rng(SEED).integers(
    0, _ms(2100, 1, 1), size=20).tolist())
def test_the_vectorised_resolve_equals_greg_resolver(now):
    rng = np.random.default_rng([SEED, now % 9973])
    n = 600
    duration = rng.integers(-1, 8, size=n).astype(np.int64)  # all six kinds, and none
    duration[:6] = np.arange(6)
    duration[6:9] = (1 << 40, -(1 << 40), 6)
    behavior = np.where(rng.random(n) < 0.7, GREG, 0).astype(np.int32) | rng.integers(0, 2, n).astype(np.int32) * 8
    behavior[:9] = GREG
    greg = greg_lanes(behavior)
    expire, length, errors, distinct = resolve_greg_columns(greg, duration, now)
    resolver = GregResolver(now)
    failed = {int(i): msg for lanes, msg in errors for i in lanes.tolist()}
    kinds = set()
    for i in range(n):
        if not greg[i]:
            assert (expire[i], length[i]) == (0, 0) and i not in failed
            continue
        want = resolver.resolve(int(duration[i]))
        if 0 <= duration[i] <= 5:
            kinds.add(int(duration[i]))
        if isinstance(want, gregorian.GregorianError):
            assert failed[i] == str(want) and (expire[i], length[i]) == (0, 0)
        else:
            assert (int(expire[i]), int(length[i])) == want and i not in failed
    assert distinct == len(kinds) == 6
    assert expire.dtype == length.dtype == np.int64
    # No calendar lane at all: zeros, nothing resolved.
    none = resolve_greg_columns(np.zeros(n, bool), duration, now)
    assert not none[0].any() and not none[1].any() and none[2:] == ([], 0)


# ---------------------------------------------------------------------
# Through a served daemon's native lane
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def daemon_at():
    """A daemon with the native edge on one device, warmed at LANES, a frozen
    clock, and the harness's own client."""
    from gubernator_tpu.cluster import fast_test_behaviors
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import Daemon
    from gubernator_tpu.utils.clock import Clock

    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0
    behaviors.multi_region_sync_wait_s = 3600.0
    clock = Clock()
    clock.freeze(T0 - 60_000)
    telemetry.set_enabled(True)
    telemetry.reset()
    saturation.reset()
    daemon = Daemon(DaemonConfig(
        listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0", cache_size=4 * SLOTS,
        global_cache_size=256, behaviors=behaviors, peer_discovery_type="static",
        native_http=True, devices=jax.devices()[:1], warmup_shapes=[LANES]), clock=clock).start()
    daemon.set_peers([daemon.peer_info])
    address = f"127.0.0.1:{daemon.gateway._edge.port}"
    http = Http(address, timeout_s=60.0)
    try:
        yield daemon, clock, http, address
    finally:
        http.close()
        daemon.close()
        telemetry.reset()
        saturation.reset()


def _lane_counts(daemon) -> "tuple[int, int]":
    stats = daemon.gateway.pump.stats()
    return stats["frames"], stats["fallbacks"]


def _send(http, address, keys, algo, behavior, hits, limit, duration) -> bytes:
    n = len(keys)
    width = len(keys[0])
    body = gubc.encode_frame(
        gubc.fixed_width_column(NAME.encode() * n, n, len(NAME)),
        gubc.fixed_width_column("".join(keys).encode(), n, width),
        np.asarray(algo, np.int32), np.asarray(behavior, np.int32), np.asarray(hits, np.int64),
        np.asarray(limit, np.int64), np.asarray(duration, np.int64))
    return http.roundtrip(gubc.http_request(address, gubc.COLUMNS_CONTENT_TYPE, body))


def test_the_native_lane_keeps_every_calendar_frame_and_answers_the_oracle(daemon_at, pop, takes, expected):
    """The cell's path at test size: load, then window, then read-back, every
    frame kept by the C++ lane from the first to the last."""
    daemon, clock, http, address = daemon_at
    before = http.get_json("/debug/device")
    compiles_before = before["compileTotal"]
    frames_before, fallbacks_before = _lane_counts(daemon)
    phases_before = http.get_json("/debug/latency")["phases"]
    answers = []
    for idx, hits, now in takes:
        clock.freeze(now)
        body = http.roundtrip(gen_frames.frame_payload(pop, idx, hits, address))
        answers.append(np.stack(gen_frames.decode(body, LANES), axis=1))
    assert _wrong(answers, expected) == []
    frames, fallbacks = _lane_counts(daemon)
    assert frames - frames_before == len(takes) and fallbacks == fallbacks_before == 0
    device = http.get_json("/debug/device")
    grown = {k: device["mesh"][k] - before["mesh"][k] for k in device["mesh"]}
    assert grown["dispatches"] == grown["wideDispatches"] == len(takes)
    assert grown["lanes"] == grown["calendarLanes"] == len(takes) * LANES
    assert grown["laneWireDispatches"] == 0
    # Ready for a monthly quota at `listening`: nothing compiled in a request.
    assert device["steadyRecompiles"] == 0 and device["compileTotal"] == compiles_before
    assert LABEL_WIDE in device["startup"]["programs"]
    status = http.get_json("/debug/status")
    assert status["wire"]["calendarLanes"] == device["mesh"]["calendarLanes"]
    assert status["wire"]["wideDispatches"] == device["mesh"]["wideDispatches"]
    latency = http.get_json("/debug/latency")
    assert {"phase": "calendar.resolve", "depth": 0} in latency["waterfall"]
    resolves = latency["phases"]["calendar.resolve"]["count"] - phases_before.get(
        "calendar.resolve", {"count": 0})["count"]
    assert resolves == len(takes)  # one a take, in front of its dispatch
    scraped = http.scrape()
    assert metric_sum(scraped, "gubernator_calendar_lanes_total") == device["mesh"]["calendarLanes"]
    assert metric_sum(scraped, "gubernator_wide_dispatches_total") == device["mesh"]["wideDispatches"]
    assert metric_sum(scraped, NATIVE_INGRESS, '"fallbacks"') == 0
    assert http.get_json("/debug/audit")["violationTotal"] == 0


def _mixed_frame(prefix: str, n: int = 64):
    """Calendar and plain lanes side by side: days, months, an hour's plain
    duration; token and leaky; every fourth key twice."""
    lane = np.arange(n)
    keys = [f"{prefix}{i - (i % 4 == 3):06d}" for i in lane]
    kind = lane % 3
    behavior = np.where(kind == 2, 0, GREG).astype(np.int32)
    duration = np.choose(kind, [DAYS, MONTHS, 3_600_000]).astype(np.int64)
    algo = ((lane // 3) % 2).astype(np.int32)
    limit = np.full(n, 5, np.int64)
    return keys, algo, behavior, limit, duration


def test_a_frame_that_mixes_calendar_and_plain_lanes_stays_native(daemon_at):
    daemon, clock, http, address = daemon_at
    keys, algo, behavior, limit, duration = _mixed_frame("mixed")
    cache = orc.OracleCache()
    frames_before, fallbacks_before = _lane_counts(daemon)
    mesh_before = http.get_json("/debug/device")["mesh"]
    now = T0 + 3_600_000
    for step, hits in enumerate((1, 3, 0, 2)):
        now += 7_000
        clock.freeze(now)
        h = np.full(len(keys), hits, np.int64)
        got = np.stack(gubc.decode_answer_frame(
            _send(http, address, keys, algo, behavior, h, limit, duration), len(keys)), axis=1)
        want = _oracle_rows(cache, keys, algo, behavior, h, limit, duration, now)
        assert (got == want).all(), (step, np.flatnonzero((got != want).any(axis=1))[:5])
    assert _lane_counts(daemon) == (frames_before + 4, fallbacks_before)
    mesh = http.get_json("/debug/device")["mesh"]
    calendar_lanes = int((behavior == GREG).sum())
    assert mesh["calendarLanes"] - mesh_before["calendarLanes"] == 4 * calendar_lanes
    assert mesh["lanes"] - mesh_before["lanes"] == 4 * len(keys)


@pytest.mark.parametrize("bad,message", [
    (WEEKS, gregorian.ERR_WEEKS), (6, gregorian.ERR_INVALID), (-1, gregorian.ERR_INVALID),
    (86_400_000, gregorian.ERR_INVALID)])
def test_a_duration_upstream_refuses_sends_the_frame_to_python_whole(daemon_at, bad, message):
    """The Python path owns the error's wording: the bad lanes answer
    upstream's error, lane by lane, and the good lanes the oracle's."""
    daemon, clock, http, address = daemon_at
    keys, algo, behavior, limit, duration = _mixed_frame(f"bad{bad % 97:02d}x")
    bad_lanes = np.flatnonzero(behavior == GREG)[[1, 5]]
    duration[bad_lanes] = bad
    frames_before, fallbacks_before = _lane_counts(daemon)
    now = T0 + 7_200_000
    clock.freeze(now)
    hits = np.ones(len(keys), np.int64)
    result = wire.decode_ingress_result_frame(
        _send(http, address, keys, algo, behavior, hits, limit, duration))
    assert _lane_counts(daemon) == (frames_before, fallbacks_before + 1)
    assert sorted(result.overrides) == bad_lanes.tolist()
    assert {r.error for r in result.overrides.values()} == {message}
    good = np.setdiff1d(np.arange(len(keys)), bad_lanes)
    want = _oracle_rows(
        orc.OracleCache(), [keys[i] for i in good], algo[good], behavior[good], hits[good],
        limit[good], duration[good], now)
    got = np.stack([result.status, result.limit, result.remaining, result.reset_time], axis=1)[good]
    assert (got == want).all()
    # A plain lane whose duration is such a number is no calendar lane: native.
    behavior[:] = 0
    duration[:] = max(bad, 1)
    _send(http, address, [k + "p" for k in keys], algo, behavior, hits, limit, duration)
    assert _lane_counts(daemon) == (frames_before + 1, fallbacks_before + 1)


@pytest.mark.parametrize("bit", [int(Behavior.GLOBAL), int(Behavior.MULTI_REGION)])
def test_global_and_multi_region_lanes_stay_native_with_or_without_the_calendar(daemon_at, bit):
    """Since PR 41 (tests/test_mixed_cell.py, which also holds the two-node
    ring where they still fall back): in a one-node ring the lane is the
    owner's own, and a calendar lane that carries the bit is resolved and
    answered like its plain neighbours."""
    daemon, clock, http, address = daemon_at
    now = T0 + 10_800_000
    clock.freeze(now)
    keys, algo, behavior, limit, duration = _mixed_frame(f"slow{bit:02d}x", n=16)
    cache = orc.OracleCache()
    hits = np.ones(len(keys), np.int64)
    for with_calendar in (False, True):
        lanes = np.flatnonzero((behavior == GREG) == with_calendar)[:2]
        beh = behavior.copy()
        beh[lanes] |= bit
        before = _lane_counts(daemon)
        got = np.stack(gubc.decode_answer_frame(
            _send(http, address, keys, algo, beh, hits, limit, duration), len(keys)), axis=1)
        assert _lane_counts(daemon) == (before[0] + 1, before[1])
        assert (got == _oracle_rows(cache, keys, algo, beh, hits, limit, duration, now)).all()


# ---------------------------------------------------------------------
# The calendar's edges, under a frozen clock, through the native lane
# ---------------------------------------------------------------------
EDGES = {
    "last-ms-of-a-day": _ms(2026, 3, 14, 23, 59, 59, ms=999),
    "first-ms-of-a-day": _ms(2026, 3, 15),
    "28-day-month": _ms(2026, 2, 10, 8),
    "29-day-month": _ms(2028, 2, 29, 23, 59, 59, ms=999),
    "30-day-month": _ms(2026, 4, 30, 23, 59, 59, ms=998),
    "31-day-month": _ms(2026, 7, 31, 23, 59, 59, ms=999),
    "day-1-of-a-31-day-month": _ms(2026, 1, 1),
    "last-ms-of-a-year": _ms(2026, 12, 31, 23, 59, 59, ms=999),
}


@pytest.mark.parametrize("edge", sorted(EDGES))
def test_the_calendars_edges_answer_the_oracle(daemon_at, edge):
    """Each instant, then one and two milliseconds on (over the boundary
    where the instant is an interval's last), then a day later: buckets end
    on their interval's last millisecond and start anew on the next."""
    daemon, clock, http, address = daemon_at
    start = EDGES[edge]
    n = 64
    lane = np.arange(n)
    algo = ((lane // 4) % 2).astype(np.int32)
    # Every token key twice; a leaky key once: the later lanes of a group that
    # CREATES a calendar leaky bucket answer the creating lane's reset_time
    # where upstream's second request, finding the bucket, answers now + rate
    # (the two forms are one for a plain duration; PERF.md section 7).
    keys = [f"{edge[:6]}{start % 100_000:05d}x{(i if a else i // 2):04d}" for i, a in zip(lane, algo)]
    duration = np.where((lane // 2) % 2 == 0, DAYS, MONTHS).astype(np.int64)
    behavior = np.full(n, GREG, np.int32)
    limit = np.where((lane // 8) % 2 == 0, 3, 1_000_000).astype(np.int64)
    cache = orc.OracleCache()
    frames_before, fallbacks_before = _lane_counts(daemon)
    steps = [(start, 1), (start + 1, 1), (start + 2, 2), (start + 86_400_000, 1), (start + 86_400_001, 0)]
    for now, hits in steps:
        clock.freeze(now)
        h = np.full(n, hits, np.int64)
        got = np.stack(gubc.decode_answer_frame(
            _send(http, address, keys, algo, behavior, h, limit, duration), n), axis=1)
        want = _oracle_rows(cache, keys, algo, behavior, h, limit, duration, now)
        assert (got == want).all(), (edge, now - start, np.flatnonzero((got != want).any(axis=1))[:5])
        if now == start:
            first = want
    assert _lane_counts(daemon) == (frames_before + len(steps), fallbacks_before)
    token = algo == 0
    at = _dt.datetime.fromtimestamp(start / 1000, tz=_dt.timezone.utc)
    day_end = _ms(at.year, at.month, at.day) + 86_400_000 - 1
    month_end = _ms(at.year, at.month, 1) + _calendar.monthrange(at.year, at.month)[1] * 86_400_000 - 1
    assert (first[token & (duration == DAYS), 3] == day_end).all()
    assert (first[token & (duration == MONTHS), 3] == month_end).all()
    if edge == "day-1-of-a-31-day-month":
        assert month_end - start > I32_MAX  # an expiry no i32 delta holds
    if edge.startswith("last-ms") or edge in ("29-day-month", "31-day-month"):
        assert day_end == start  # created on its interval's last millisecond


# ---------------------------------------------------------------------
# The names, and the cell's files
# ---------------------------------------------------------------------
def test_the_resolve_is_a_top_level_phase_between_the_admit_and_the_plan():
    names = [p for p, _ in saturation.WATERFALL]
    at = names.index("calendar.resolve")
    assert saturation.WATERFALL[at] == ("calendar.resolve", 0)
    assert names.index("pump.admit") < at < names.index("dispatch.prepare")


def test_the_pump_keeps_the_calendar_bit_off_both_fallback_masks():
    from gubernator_tpu.gateway import NativeIngressPump

    for express in (False, True):
        for all_self in (False, True):
            assert not NativeIngressPump.fallback_mask(all_self, express) & GREG
        # A ring with another node: GLOBAL and MULTI_REGION still fall back.
        mask = NativeIngressPump.fallback_mask(False, express)
        assert mask & int(Behavior.GLOBAL) and mask & int(Behavior.MULTI_REGION)


def test_the_cells_files_say_what_the_issue_says():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    candidate = _cell_json("candidates", "greg-10m.frames.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == "greg-10m")
    assert cell == candidate["workload"] and entry == candidate["config"]  # letter for letter
    assert bench["workloads"].index(cell) == 5 and bench["configs"].index(entry) == 4  # appended, by PR 39
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("greg-10m", "frames-pool4k", 1)
    config = _cell_json("configs", "greg-10m.json")
    assert config["source"] == entry["source"] and entry["reduced"] == config["reduced"] == []
    assert config["population"]["resident_keys"] == 10_000_000
    assert config["population"]["calendar"] == dict(
        config["population"]["calendar"], share=1.0, units={"days": 0.5, "months": 0.5}, horizon_s=180)
    assert config["env"] == {"GUBER_NATIVE_HTTP": "1", "GUBER_CACHE_SIZE": "16777216"}
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name, metric in by_name.items():
        if BYPASS in metric.get("workloads", []):  # req_p99_ms too: two sets of six runs spread 5.7% and 0.8% (PERF.md section 2)
            assert CELL in metric["workloads"], name
    assert CELL in by_name["kernel.apply_roofline"]["workloads"]
    # The cell rides the dictionary wire (64 configurations a frame, one upload
    # a dispatch): the wire's and the stage's metrics have something to read.
    for name in ("wire.lane_share", "wire.configs_per_dispatch", "wire.uploads_per_dispatch",
                 "wire.upload_ms_per_dispatch", "mesh.stage_ms_per_dispatch"):
        assert CELL in by_name[name]["workloads"], name  # appended by PR 39; PRs 41 and 45 appended theirs
    # Nothing to read: the native lane bypasses the batcher, and there is one shard.
    for name in ("batcher.queue_p99_ms", "mesh.pad_fill", "mesh.shard_skew"):
        assert CELL not in by_name[name]["workloads"], name
    for name in NEW_METRICS:
        metric = by_name[name]
        assert metric["workloads"][:2] == [CELL, BYPASS]
        spec = _cell_json("layer_metrics", name + ".json")
        assert spec["reader"] in ("mesh_tally", "phase_ms_per", "counter_share")
        assert (spec["layer"], spec["unit"], spec["source"], spec["moves"], spec["better"]) == (
            metric["layer"], metric["unit"], metric["source"], metric["moves"], metric["better"])
        if spec["reader"] == "mesh_tally":
            assert "0" in spec["what"] and "not nothing" in spec["what"]
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == list(NEW_METRICS) and at == 30


# ---------------------------------------------------------------------
# The readers of the four new metrics, on snapshots written out here
# ---------------------------------------------------------------------
def _snap(mesh=None, resolve=None, ingress=None):
    device = {} if mesh is None else {"mesh": mesh}
    phases = {} if resolve is None else {"calendar.resolve": {"count": resolve[0], "sum_ms": resolve[1]}}
    rows = [] if ingress is None else [
        (NATIVE_INGRESS, '{stat="frames"}', float(ingress[0])),
        (NATIVE_INGRESS, '{stat="fallbacks"}', float(ingress[1])),
        (NATIVE_INGRESS, '{stat="lanes"}', 4096.0 * ingress[0]),
    ]
    return {"device": device, "latency": {"phases": phases}, "metrics": rows}


def _read(name, ctx):
    spec = _cell_json("layer_metrics", name + ".json")
    reader = {"mesh_tally": mesh_tally, "phase_ms_per": phase_ms_per, "counter_share": counter_share}
    return reader[spec["reader"]].read(ctx, spec["params"])


LOADED = {"shards": 1, "dispatches": 2_442, "lanes": 2_442 * 4096, "calendarLanes": 2_442 * 4096,
          "wideDispatches": 2_442}
# 2,000 frames more, 1,500 of them wide, three lanes in four of all calendar quotas.
WINDOW = {"shards": 1, "dispatches": 4_442, "lanes": 4_442 * 4096,
          "calendarLanes": 2_442 * 4096 + 1_500 * 4096, "wideDispatches": 2_442 + 1_500}


@pytest.mark.parametrize("name,want", [
    ("calendar.lane_share", 75.0),
    ("wire.wide_share", 75.0),
    ("calendar.resolve_ms_per_dispatch", 0.09),
    ("ingress.native_frame_share", 80.0),
])
def test_the_new_readers_give_the_values_reckoned_by_hand(name, want):
    ctx = {"before": _snap(LOADED, (2_442, 200.0), (2_442, 10)),
           "after": _snap(WINDOW, (4_442, 380.0), (2_442 + 1_600, 10 + 400)), "requests": 2_000}
    assert _read(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_new_readers_read_nothing_where_nothing_was_dispatched(name):
    """No `mesh` block, no phase, no counter, or nothing between the
    snapshots: None, and no exception."""
    assert _read(name, {"before": _snap(), "after": _snap(), "requests": 0}) is None
    same = _snap(WINDOW, (4_442, 380.0), (4_042, 410))
    assert _read(name, {"before": same, "after": same, "requests": 0}) is None


def test_a_program_from_before_the_counters_reads_0_and_no_phase():
    """The parent: a `mesh` block without `calendarLanes` and `wideDispatches`
    reads 0 (`mesh_tally` takes a missing counter for 0), no `calendar.resolve`
    phase reads nothing, and every frame a fallback reads a share of 0."""
    old = {k: v for k, v in LOADED.items() if k not in ("calendarLanes", "wideDispatches")}
    new = dict(old, dispatches=4_442, lanes=4_442 * 4096)
    ctx = {"before": _snap(old, None, (0, 2_442)), "after": _snap(new, None, (0, 4_442)), "requests": 2_000}
    assert _read("calendar.lane_share", ctx) == 0.0
    assert _read("wire.wide_share", ctx) == 0.0
    assert _read("calendar.resolve_ms_per_dispatch", ctx) is None
    assert _read("ingress.native_frame_share", ctx) == 0.0
