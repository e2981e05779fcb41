"""Cost observatory (profiling.py + the metrics/gateway/service/config
wiring): the continuous host sampler (scope/tag fold semantics, the
compiled-out discipline, the named-attribution integration gate), the
per-tenant cost ledger (Zipf-oracle accuracy, exact other-rollup
conservation through promotion/eviction churn, bounded metric
cardinality under 10k distinct names, the audit-pairing rule), the
/debug/pprof & /debug/tenants surfaces, the /debug/profile host-window
pairing, and config plumbing."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from gubernator_tpu import audit as audit_mod
from gubernator_tpu import profiling, saturation, tracing
from gubernator_tpu.gateway import handle_request
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.saturation import phase
from gubernator_tpu.service import (
    ColumnarResult,
    IngressColumns,
    ServiceConfig,
    V1Service,
)
from gubernator_tpu.types import PeerInfo, RateLimitResponse


@pytest.fixture(autouse=True)
def _clean_plane():
    prev = profiling.enabled()
    profiling.set_enabled(True)
    profiling.reset()
    saturation.reset()
    yield
    profiling.reset()
    saturation.reset()
    profiling.set_enabled(prev)


def _cols(names, hits=None, uk=None):
    n = len(names)
    return IngressColumns(
        names=list(names),
        unique_keys=list(uk) if uk is not None else [f"k{i}" for i in range(n)],
        algorithm=np.zeros(n, np.int32),
        behavior=np.zeros(n, np.int32),
        hits=(
            np.asarray(hits, np.int64) if hits is not None
            else np.ones(n, np.int64)
        ),
        limit=np.full(n, 1_000_000, np.int64),
        duration=np.full(n, 3_600_000, np.int64),
    )


def _service(**kw):
    svc = V1Service(ServiceConfig(cache_size=512, **kw))
    svc.set_peers([PeerInfo(grpc_address="127.0.0.1:1", is_owner=True)])
    return svc


def _assert_conserves(snap):
    """The rollup invariant the ledger promises: for every stat,
    top-K rows + other == totals EXACTLY (audit-style, but two-sided
    because nothing in the ledger is lag-tolerant)."""
    for stat in ("hits", "lanes", "overLimit", "shed", "ingressBytes"):
        parts = sum(r[stat] for r in snap["topk"]) + snap["other"][stat]
        assert parts == snap["totals"][stat], (stat, snap)


# ---------------------------------------------------------------------
# Sampler: scopes, tags, fold, compiled-out discipline
# ---------------------------------------------------------------------
def test_phase_tag_nesting_restores_and_pops():
    ident = threading.get_ident()
    assert ident not in profiling._scopes
    with phase("ingress.parse"):
        assert profiling._scopes[ident] == "ingress.parse"
        with phase("response.encode"):
            assert profiling._scopes[ident] == "response.encode"
        assert profiling._scopes[ident] == "ingress.parse"
    # Outermost exit POPS (thread idents recycle; a parked None would
    # leak an entry per pool thread).
    assert ident not in profiling._scopes


def test_phase_with_profiler_off_leaves_no_tag():
    profiling.set_enabled(False)
    try:
        with phase("ingress.parse"):  # the one-branch compiled-out contract
            assert threading.get_ident() not in profiling._scopes
    finally:
        profiling.set_enabled(True)


def test_sampler_folds_scoped_and_tagged_threads():
    ready = threading.Event()
    release = threading.Event()

    def scoped_worker():
        with phase("dispatch.launch"):
            ready.set()
            release.wait(10)

    def tagged_worker():
        profiling.tag_thread("epoll.wait")
        profiling.set_program("mesh.solo.narrow")
        ready2.set()
        release.wait(10)

    ready2 = threading.Event()
    t1 = threading.Thread(target=scoped_worker, name="scoped")
    t2 = threading.Thread(target=tagged_worker, name="tagged")
    t1.start(), t2.start()
    assert ready.wait(10) and ready2.wait(10)
    s = profiling.Sampler()  # not started: driven manually
    try:
        for _ in range(5):
            s.sample_once()
    finally:
        release.set()
        t1.join(), t2.join()
    win = s.merged(60)
    assert win.samples > 0
    assert win.phases.get("dispatch.launch", 0) >= 5
    assert win.phases.get("epoll.wait", 0) >= 5
    # The program label rides beside the phase (the PR 9 mirror).
    assert win.programs.get("mesh.solo.narrow", 0) >= 5
    # Collapsed lines carry phase;stack count and fold the wait frames.
    stacks = {tag for (tag, _stack) in win.stacks}
    assert "dispatch.launch" in stacks and "epoll.wait" in stacks


def test_worker_suffix_strip_folds_pools():
    assert profiling._strip_worker_suffix("ThreadPoolExecutor-0_3") == (
        "ThreadPoolExecutor-0"
    )
    assert profiling._strip_worker_suffix("drainer-7") == "drainer"
    assert profiling._strip_worker_suffix("epoll") == "epoll"
    # All-digit names survive (never fold to the empty tag).
    assert profiling._strip_worker_suffix("123") == "123"


def test_profile_snapshot_and_collapsed_render():
    release = threading.Event()
    started = threading.Event()

    def worker():
        with phase("ingress.parse"):
            started.set()
            release.wait(10)

    t = threading.Thread(target=worker)
    t.start()
    assert started.wait(10)
    s = profiling._get_sampler(start=True)
    try:
        for _ in range(8):
            s.sample_once()
    finally:
        release.set()
        t.join()
    doc = profiling.profile_snapshot(seconds=60, top=5)
    assert doc["samples"] > 0
    assert doc["phases"].get("ingress.parse", 0) >= 8
    assert len(doc["topStacks"]) <= 5
    assert doc["namedFraction"] > 0
    text = profiling.collapsed(60)
    lines = [ln for ln in text.splitlines() if ln]
    assert lines, text
    for ln in lines:
        stack, _, count = ln.rpartition(" ")
        assert stack and count.isdigit(), ln


# ---------------------------------------------------------------------
# Tenant ledger: Zipf oracle, conservation, cardinality
# ---------------------------------------------------------------------
def test_tenant_zipf_oracle_within_sketch_error():
    rng = np.random.RandomState(11)
    n_names, n_lanes = 2000, 40_000
    ranks = np.minimum(
        rng.zipf(1.3, size=n_lanes) - 1, n_names - 1
    ).astype(np.int64)
    names = [f"tenant:{r}" for r in range(n_names)]
    true_counts = np.bincount(ranks, minlength=n_names)
    led = profiling.TenantLedger(topk=8, width=4096, depth=4)
    for lo in range(0, n_lanes, 1000):
        batch = ranks[lo:lo + 1000]
        led.fold_admit(_cols([names[r] for r in batch]))
    snap = led.snapshot()
    assert snap["totals"]["hits"] == n_lanes
    assert snap["totals"]["lanes"] == n_lanes
    _assert_conserves(snap)
    got = {r["tenant"]: r for r in snap["topk"]}
    true_top = np.argsort(true_counts)[::-1]
    # The heaviest tenants must be tracked, with count-min's one-sided
    # error on the ranking estimate: estimate >= truth, within a small
    # overcount of total traffic.
    for r in true_top[:3]:
        name = names[int(r)]
        assert name in got, (name, list(got)[:8])
        assert got[name]["estimate"] >= true_counts[r]
        assert got[name]["estimate"] <= true_counts[r] + n_lanes * 0.01


def test_tenant_cardinality_bounded_under_10k_names():
    led = profiling.TenantLedger(topk=8, width=4096, depth=4)
    # 10k distinct names, one lane each, folded in column batches.
    for lo in range(0, 10_000, 500):
        led.fold_admit(_cols([f"n{i}" for i in range(lo, lo + 500)]))
    snap = led.snapshot()
    assert snap["trackedTenants"] <= 8
    assert len(snap["topk"]) <= 8
    _assert_conserves(snap)

    # And the EXPORTED cardinality holds: <= K tenant label values on
    # gubernator_tenant_cost plus the single `other` rollup family.
    class _Svc:
        tenants = led

    m = Metrics()
    m.observe_cost(_Svc())
    text = m.render().decode()
    tenants = {
        line.split('tenant="', 1)[1].split('"', 1)[0]
        for line in text.splitlines()
        if line.startswith("gubernator_tenant_cost{")
    }
    assert 0 < len(tenants) <= 8, tenants
    assert "gubernator_tenant_other" in text
    assert "gubernator_tenant_total" in text


def test_tenant_conservation_through_eviction_churn():
    led = profiling.TenantLedger(topk=2, width=256, depth=2)
    rng = np.random.RandomState(3)
    # Rotating hot tenants force promote/evict churn at topk=2; the
    # rollup must conserve after EVERY batch, not just at the end.
    for round_ in range(30):
        hot = f"hot{round_ % 5}"
        names = [hot] * 40 + [f"cold{rng.randint(50)}" for _ in range(10)]
        led.fold_admit(_cols(names, hits=rng.randint(1, 4, size=50)))
        _assert_conserves(led.snapshot())


def test_tenant_outcome_and_shed_folds():
    led = profiling.TenantLedger(topk=4)
    cols = _cols(["a", "a", "b", "c"], hits=[1, 2, 3, 4])
    ctx = led.fold_admit(cols)
    assert ctx is not None
    res = ColumnarResult.empty(4)
    res.status = np.array([1, 0, 1, 0], np.int32)
    # A sparse override flips lane 3 to OVER_LIMIT; lane 0's array says
    # over but an errored override would cancel it.
    res.overrides[3] = RateLimitResponse(status=1)
    led.fold_outcome(ctx, res)
    led.fold_shed(ctx, np.array([0, 1]))  # tenant a sheds two lanes
    snap = led.snapshot()
    rows = {r["tenant"]: r for r in snap["topk"]}
    assert snap["totals"]["hits"] == 10
    assert rows["a"]["overLimit"] == 1  # lane 0 (array)
    assert rows["b"]["overLimit"] == 1  # lane 2 (array)
    assert rows["c"]["overLimit"] == 1  # lane 3 (override)
    assert rows["a"]["shed"] == 2
    assert snap["totals"]["overLimit"] == 3
    assert snap["totals"]["shed"] == 2
    _assert_conserves(snap)
    # overLimitRate derives from lanes.
    assert rows["a"]["overLimitRate"] == pytest.approx(0.5)


def test_tenant_proportional_shares():
    led = profiling.TenantLedger(topk=4)
    led.fold_admit(_cols(["a"] * 30 + ["b"] * 10))
    profiling.note_lane_time(40, 0.8)    # 20 ms/lane
    profiling.note_queue_wait(40, 0.1)   # 0.1 s x 40 lanes / 40 lanes
    snap = led.snapshot()
    rows = {r["tenant"]: r for r in snap["topk"]}
    assert rows["a"]["laneTimeS"] == pytest.approx(30 * 0.02, rel=1e-6)
    assert rows["b"]["laneTimeS"] == pytest.approx(10 * 0.02, rel=1e-6)
    assert rows["a"]["queueS"] == pytest.approx(30 * 0.1, rel=1e-6)
    assert snap["laneTimeSPerLane"] == pytest.approx(0.02, rel=1e-6)


def test_tenant_single_and_dataclass_folds():
    led = profiling.TenantLedger(topk=4)
    led.fold_one("solo", hits=7, nbytes=100)
    snap = led.snapshot()
    assert snap["totals"]["hits"] == 7
    assert snap["totals"]["ingressBytes"] == 100  # pre-computed budget
    names = led.fold_requests([])
    assert names is None
    _assert_conserves(snap)


def test_tenant_scalar_fold_matches_vector_twin():
    """fold_one is a scalar twin of fold_admit: totals and the
    count-min sketch must agree exactly with the batch fold over the
    same lanes, and conservation must hold on both.  (The row/`other`
    SPLIT may differ — promotion moves only the current fold's
    contribution, and the scalar path folds one lane at a time.)"""
    rng = np.random.RandomState(3)
    names = [f"t{rng.zipf(1.3) % 12}" for _ in range(400)]
    uks = [f"k{i}" for i in range(400)]
    hits = rng.randint(1, 5, 400)
    a = profiling.TenantLedger(topk=4)
    b = profiling.TenantLedger(topk=4)
    a.fold_admit(_cols(names, hits=hits, uk=uks))
    for n, u, h in zip(names, uks, hits):
        b.fold_one(n, int(h),
                   len(n) + len(u) + profiling.NUMERIC_LANE_BYTES)
    assert a.totals() == b.totals()
    assert np.array_equal(a._tab, b._tab)
    sa, sb = a.snapshot(), b.snapshot()
    _assert_conserves(sa)
    _assert_conserves(sb)
    # Same est ranking feeds both: the top tenant agrees.
    assert sa["topk"][0]["tenant"] == sb["topk"][0]["tenant"]


def _few_cols(shape, names, uks, hits):
    """The few lanes of one classic call, in each shape the ledger is
    handed: plain lists, the native JSON parse (spans into the body),
    a decoded GUBC frame (blobs and offsets)."""
    if shape == "lists":
        return _cols(names, hits=hits, uk=uks)
    if shape == "json":
        from gubernator_tpu.gateway import parse_body_native

        cols = parse_body_native(json.dumps({"requests": [
            {"name": n, "uniqueKey": u, "hits": str(int(h)),
             "limit": "1000000", "duration": "3600000"}
            for n, u, h in zip(names, uks, hits)
        ]}, ensure_ascii=False).encode())
        if cols is None:
            pytest.skip("native JSON parse unavailable")
        return cols
    from gubernator_tpu import wire

    n = len(names)
    cols = wire.decode_ingress_frame(wire.encode_ingress_frame((
        list(names), list(uks), np.zeros(n, np.int32), np.zeros(n, np.int32),
        np.asarray(hits, np.int64), np.full(n, 1_000_000, np.int64),
        np.full(n, 3_600_000, np.int64),
    )))
    assert isinstance(cols, wire.FrameIngressColumns)
    return cols


@pytest.mark.parametrize("shape", ["lists", "json", "frame"])
def test_tenant_few_lane_fold_is_the_batch_fold(shape):
    """A call of at most `topk` lanes folds lane by lane
    (`_fold_few`); the same calls through the vectorized fold leave
    the same ledger, cell for cell and row for row, and hand the
    outcome folds the same context."""
    rng = np.random.RandomState(5)
    a = profiling.TenantLedger(topk=4, width=64, depth=4)  # cells collide
    b = profiling.TenantLedger(topk=4, width=64, depth=4)
    for step in range(300):
        n = int(rng.randint(1, 5))
        names = [f"tenant-{rng.zipf(1.4) % 9}" for _ in range(n)]
        uks = [f"k{rng.randint(1000)}" for _ in range(n)]
        hits = rng.randint(0, 5, n)
        cols = _few_cols(shape, names, uks, hits)
        ca, cb = a.fold_admit(cols), b._fold_batch(cols)
        assert ca.m == cb.m
        assert np.array_equal(ca.inv, cb.inv)
        assert np.array_equal(ca.uh, cb.uh) and ca.uh.dtype == cb.uh.dtype
        assert np.array_equal(ca.first, cb.first)
        over = rng.randint(0, 2, n)
        for led, ctx in ((a, ca), (b, cb)):
            led._route_stat_locked(
                "over_limit", ctx,
                np.bincount(ctx.inv, weights=over, minlength=ctx.m)
                .astype(np.int64),
            )
            led.fold_shed(ctx, np.flatnonzero(over == 0)[:1])
    assert np.array_equal(a._tab, b._tab) and a._tab.any()
    assert a.batches == b.batches == 300
    sa, sb = a.snapshot(), b.snapshot()
    assert sa == sb
    _assert_conserves(sa)
    assert len(sa["topk"]) == 4 and sa["other"]["lanes"] > 0


# ---------------------------------------------------------------------
# The native batch fold against the numpy fold it replaced
# (tests/fold_reference.py), on the cells' own shapes
# ---------------------------------------------------------------------
def _rows_of(led):
    return {
        h: (r.name, r.est, r.hits, r.lanes, r.over_limit, r.shed,
            r.ingress_bytes)
        for h, r in led._rows.items()
    }


def _assert_same_fold(a, b, ca, cb, rows=True):
    """Ledger `a` (the package's fold) and `b` (the reference's) after
    the same batches, and the contexts of the last one."""
    assert ca.m == cb.m
    assert np.array_equal(ca.inv, cb.inv) and ca.inv.dtype == cb.inv.dtype
    assert np.array_equal(ca.uh, cb.uh) and ca.uh.dtype == cb.uh.dtype
    assert np.array_equal(ca.first, cb.first)
    assert np.array_equal(a._tab, b._tab)  # cell for cell
    assert a._totals == b._totals and a.batches == b.batches
    for led in (a, b):
        _assert_conserves(led.snapshot())
    if rows:
        assert a._other == b._other
        assert _rows_of(a) == _rows_of(b)
        assert np.array_equal(a._row_hashes, b._row_hashes)


@pytest.mark.parametrize("shape", [
    "zipf-4096", "zipf-1028", "load-64", "coalesced", "names-10k",
    "hits-0-and-many",
])
def test_native_tenant_fold_is_the_numpy_fold(shape):
    """Every take of the shape through `_fold_batch` (native) and
    through the numpy reference: the count-min tables equal cell for
    cell, totals / other / rows equal, the context equal, and the
    outcome and shed folds on that context equal.  `names-10k` folds
    500 names of ONE hit each a batch, so its candidates' estimates tie
    by the hundred and which of them the rows keep is the one thing the
    two folds may answer differently: there the rows are held to
    conservation and to the same estimates, not to the same names."""
    from .fold_reference import frame_cols, ref_fold_batch, takes

    ties = shape == "names-10k"
    a = profiling.TenantLedger(topk=8, width=4096, depth=4)
    b = profiling.TenantLedger(topk=8, width=4096, depth=4)
    rng = np.random.RandomState(2)
    for names, uks, hits in takes(shape):
        cols = frame_cols(names, uks, hits)
        ca, cb = a._fold_batch(cols), ref_fold_batch(b, cols)
        _assert_same_fold(a, b, ca, cb, rows=not ties)
        res = ColumnarResult.empty(len(names))
        res.status = rng.randint(0, 2, len(names)).astype(np.int32)
        shed = np.flatnonzero(rng.randint(0, 8, len(names)) == 0)
        for led, ctx in ((a, ca), (b, cb)):
            led.fold_outcome(ctx, res)
            led.fold_shed(ctx, shed)
        _assert_same_fold(a, b, ca, cb, rows=not ties)
    assert a._tab.any() or shape == "load-64"  # a load's hits are 0
    assert a._totals["lanes"] == sum(len(t[0]) for t in takes(shape))
    if ties:
        assert sorted(r.est for r in a._rows.values()) == sorted(
            r.est for r in b._rows.values())
    assert a.batches == len(takes(shape))
    # Python's share of a fold: at most topk candidates, never the lanes.
    assert 0 <= a.candidates <= 8 * a.batches
    assert b.candidates == 0  # the reference walks every untracked name


@pytest.mark.parametrize("shape", ["lists", "json", "frame"])
def test_native_tenant_fold_every_column_shape(shape):
    """The three column shapes the ledger is handed (plain lists, the
    native JSON parse, a decoded frame), with non-ASCII names, and an
    empty take: the native fold and the reference agree on each."""
    from .fold_reference import ref_fold_batch

    rng = np.random.RandomState(9)
    a = profiling.TenantLedger(topk=4, width=64, depth=4)  # cells collide
    b = profiling.TenantLedger(topk=4, width=64, depth=4)
    assert a.fold_admit(_few_cols(shape, [], [], [])) is None
    for step in range(60):
        n = int(rng.randint(5, 40))
        names = [f"t\u00e9nant-{rng.zipf(1.4) % 9}" for _ in range(n)]
        uks = [f"k{rng.randint(1000)}" for _ in range(n)]
        hits = rng.randint(0, 5, n)
        cols = _few_cols(shape, names, uks, hits)
        _assert_same_fold(
            a, b, a.fold_admit(cols), ref_fold_batch(b, cols)
        )
    assert len(a._rows) == 4 and a._other["lanes"] > 0
    assert a.batches == 60


@pytest.mark.parametrize("shape", [
    "zipf-1028", "coalesced", "names-10k", "hits-0-and-many",
])
def test_tenant_ledger_folds_the_same_without_the_native_build(
        shape, monkeypatch):
    """A host with no compiler (`native.available()` False) still
    constructs a ledger and folds into it: `native.name_groups` and
    `native.cms_fold` answer from numpy there, the same answer position
    for position — tied candidates included — so the table, the context
    AND the rows come out equal."""
    from gubernator_tpu import native

    from .fold_reference import frame_cols, takes

    batches = [frame_cols(*t) for t in takes(shape)]
    a = profiling.TenantLedger(topk=8, width=4096, depth=4)
    ctxs = [a.fold_admit(cols) for cols in batches]
    monkeypatch.setattr(native, "_get_lib", lambda: None)
    assert not native.available()
    b = profiling.TenantLedger(topk=8, width=4096, depth=4)
    for cols, ca in zip(batches, ctxs):
        cb = b.fold_admit(cols)
        for col in ("inv", "uh", "first"):
            x, y = getattr(ca, col), getattr(cb, col)
            assert x.dtype == y.dtype and np.array_equal(x, y)
    _assert_same_fold(a, b, ca, cb)  # the ledgers, after the same takes
    assert a.candidates == b.candidates


def test_native_tenant_fold_threads_lose_no_count():
    """Two threads folding batches into one ledger: the lock covers the
    native call, so every cell and every total is the serial sum."""
    from .fold_reference import frame_cols, takes

    batches = [frame_cols(*t) for t in takes("hits-0-and-many")]
    shared = profiling.TenantLedger(topk=8, width=256, depth=4)
    serial = profiling.TenantLedger(topk=8, width=256, depth=4)
    reps = 40

    def run():
        for _ in range(reps):
            for cols in batches:
                shared.fold_admit(cols)

    ts = [threading.Thread(target=run) for _ in range(2)]
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # hand the interpreter over mid-fold
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(prev)
    assert not any(t.is_alive() for t in ts)
    for _ in range(2 * reps):
        for cols in batches:
            serial.fold_admit(cols)
    assert np.array_equal(shared._tab, serial._tab)
    assert shared._totals == serial._totals
    assert shared.batches == serial.batches == 2 * reps * len(batches)
    _assert_conserves(shared.snapshot())


# ---------------------------------------------------------------------
# Service pairing: every audit ingress note has a tenant fold beside it
# ---------------------------------------------------------------------
def test_service_tenant_folds_reconcile_with_audit():
    svc = _service()
    try:
        base = audit_mod.ledger_snapshot()
        body = json.dumps({"requests": [
            {"name": f"ten{i % 3}", "uniqueKey": f"k{i}", "hits": "2",
             "limit": "100", "duration": "60000"} for i in range(30)
        ]}).encode()
        st, _, _ = handle_request(svc, "POST", "/v1/GetRateLimits", body)
        assert st == 200
        led = audit_mod.ledger_snapshot()
        ingress_delta = (
            led.get("ingress_hits", 0) - base.get("ingress_hits", 0)
            + led.get("peer_ingress_hits", 0)
            - base.get("peer_ingress_hits", 0)
        )
        totals = svc.tenants.totals()
        assert totals["hits"] == ingress_delta == 60
        assert totals["lanes"] == 30
        snap = svc.tenants.snapshot()
        assert {r["tenant"] for r in snap["topk"]} == {
            "ten0", "ten1", "ten2"
        }
        _assert_conserves(snap)
    finally:
        svc.close()


# ---------------------------------------------------------------------
# /debug surfaces + the >= 80% named-attribution integration gate
# ---------------------------------------------------------------------
def test_pprof_named_fraction_on_loaded_daemon():
    """The acceptance gate: on a daemon under load, >= 80% of profiler
    samples attribute to a NAMED phase/thread tag, not `unknown`."""
    svc = _service()
    stop = threading.Event()

    def worker(wid):
        i = 0
        while not stop.is_set():
            body = json.dumps({"requests": [
                {"name": f"load{wid}", "uniqueKey": f"k{i}:{j}",
                 "hits": "1", "limit": "1000000",
                 "duration": "60000"} for j in range(32)
            ]}).encode()
            handle_request(svc, "POST", "/v1/GetRateLimits", body)
            i += 1

    threads = [
        threading.Thread(target=worker, args=(w,), name=f"load-{w}")
        for w in range(4)
    ]
    s = profiling._get_sampler(start=True)

    def tick_for(seconds):
        deadline = time.time() + seconds
        while time.time() < deadline:
            s.sample_once()
            time.sleep(0.005)

    try:
        for t in threads:
            t.start()
        # The sampler learns thread names once a second: until then a
        # thread this young folds as `unknown`, which says nothing about
        # a daemon (its threads live for hours).  So load for two seconds
        # first, then judge the two seconds after — and only those: the
        # ring is process-wide and still holds earlier tests' samples.
        tick_for(2.2)
        tick_for(2.0)
        st, ctype, payload = handle_request(
            svc, "GET", "/debug/pprof?format=json&seconds=2", b""
        )
        assert st == 200 and ctype == "application/json"
        doc = json.loads(payload)
        assert doc["samples"] >= 40, doc["samples"]
        assert doc["namedFraction"] >= 0.8, doc["phases"]
        # The collapsed view serves the same window as text.
        st, ctype, text = handle_request(
            svc, "GET", "/debug/pprof?seconds=2", b""
        )
        assert st == 200 and ctype.startswith("text/plain")
        assert text.decode().splitlines()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        svc.close()


def test_debug_tenants_and_status_surfaces():
    svc = _service()
    try:
        body = json.dumps({"requests": [
            {"name": "acme", "uniqueKey": f"k{i}", "hits": "1",
             "limit": "100", "duration": "60000"} for i in range(8)
        ]}).encode()
        st, _, _ = handle_request(svc, "POST", "/v1/GetRateLimits", body)
        assert st == 200
        st, ctype, payload = handle_request(svc, "GET", "/debug/tenants", b"")
        assert st == 200 and ctype == "application/json"
        doc = json.loads(payload)
        assert doc["topk"][0]["tenant"] == "acme"
        assert doc["topkLimit"] >= 1
        _assert_conserves(doc)
        st, _, payload = handle_request(svc, "GET", "/debug/status", b"")
        status = json.loads(payload)
        assert status["tenants"]["topk"][0]["tenant"] == "acme"
        assert status["profile"]["enabled"] is True
        assert status["profile"]["hz"] == profiling.hz()
        # The scrape carries the new families.
        st, _, metrics = handle_request(svc, "GET", "/metrics", b"")
        text = metrics.decode()
        for fam in ("gubernator_tenant_cost", "gubernator_tenant_other",
                    "gubernator_tenant_total", "gubernator_profile_hz"):
            assert fam in text, fam
    finally:
        svc.close()


def test_debug_profile_pairs_host_window(tmp_path, monkeypatch):
    """POST /debug/profile answers with the host-profiler pairing: the
    live pprof URL covering the same seconds, and the collapsed host
    window written beside the device trace when the run completes."""
    from gubernator_tpu import gateway

    monkeypatch.chdir(tmp_path)
    prev = tracing.sample_rate()
    tracing.set_sample_rate(1.0)
    try:
        st, _, body = gateway.handle_request(
            None, "POST", "/debug/profile", b'{"durationMs": 50}'
        )
        assert st == 202, body
        doc = json.loads(body)
        assert doc["hostPprof"] == "/debug/pprof?seconds=1"
        assert doc["hostProfile"] == f"{doc['logDir']}/host_profile.collapsed"
        t = gateway._profile_state["thread"]
        if t is not None:
            t.join(timeout=60)
        assert os.path.exists(doc["hostProfile"])
    finally:
        tracing.set_sample_rate(prev)


# ---------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------
def test_config_knobs_loud_validation():
    from gubernator_tpu.config import setup_daemon_config

    conf = setup_daemon_config(env={
        "GUBER_PROFILE": "0", "GUBER_PROFILE_HZ": "101",
        "GUBER_TENANT_TOPK": "32",
    })
    assert conf.behaviors.profile is False
    assert conf.behaviors.profile_hz == 101.0
    assert conf.behaviors.tenant_topk == 32
    # Defaults (the shipped always-on plane).
    conf = setup_daemon_config(env={})
    assert conf.behaviors.profile is True
    assert conf.behaviors.profile_hz == 67.0
    assert conf.behaviors.tenant_topk == 16
    for bad in (
        {"GUBER_PROFILE_HZ": "fast"},
        {"GUBER_PROFILE_HZ": "0"},        # 0 is GUBER_PROFILE=0's job
        {"GUBER_PROFILE_HZ": "5000"},     # loud, not clamped
        {"GUBER_TENANT_TOPK": "0"},
        {"GUBER_TENANT_TOPK": "99999"},
        {"GUBER_TENANT_TOPK": "many"},
    ):
        with pytest.raises(ValueError):
            setup_daemon_config(env=bad)


def test_service_tenant_topk_from_behaviors():
    from gubernator_tpu.cluster import fast_test_behaviors

    beh = fast_test_behaviors()
    beh.tenant_topk = 3
    svc = V1Service(ServiceConfig(cache_size=512, behaviors=beh))
    try:
        assert svc.tenants.topk == 3
    finally:
        svc.close()
