"""Saturation & SLO observability plane (saturation.py + the metrics /
gateway / service wiring): latency attribution reservoirs, ceil-rank
percentiles, the SLO burn-rate engine, the hot-key sketch, occupancy
telemetry vs an oracle (with the ZERO-extra-device-dispatch pin), the
/debug/status|latency|hotkeys surfaces on both gateways, and the
sample-0 wire-parity contract with the plane active."""

import json
import sys
import threading

import numpy as np
import pytest

from gubernator_tpu import native, saturation, tracing, wire
from gubernator_tpu.gateway import GatewayServer, handle_request
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.service import IngressColumns, ServiceConfig, V1Service
from gubernator_tpu.types import PeerInfo

from .conftest import one_device_store

T0 = 1_573_430_430_000


@pytest.fixture(autouse=True)
def _clean_plane():
    saturation.reset()
    tracing.reset()
    yield
    saturation.reset()
    tracing.reset()


def _cols(n, salt=0, name="obs"):
    return IngressColumns(
        names=[name] * n,
        unique_keys=[f"k{salt}:{i}" for i in range(n)],
        algorithm=np.zeros(n, np.int32),
        behavior=np.zeros(n, np.int32),
        hits=np.ones(n, np.int64),
        limit=np.full(n, 1_000_000, np.int64),
        duration=np.full(n, 3_600_000, np.int64),
    )


def _service(**kw):
    svc = V1Service(ServiceConfig(cache_size=512, **kw))
    svc.set_peers([PeerInfo(grpc_address="127.0.0.1:1", is_owner=True)])
    return svc


# ---------------------------------------------------------------------
# Ceil-rank percentiles
# ---------------------------------------------------------------------
def test_percentile_nearest_rank():
    # n=100, q=0.99: nearest rank is 99 (1-based) -> index 98.  The old
    # floor form min(n-1, int(n*q)) indexed 99 — a different sample.
    vals = list(range(100))
    assert saturation.percentile(vals, 0.99) == 98
    assert saturation.percentile_rank(100, 0.99) == 98
    # Small n: ceil rank keeps the tail honest.
    assert saturation.percentile([1, 2, 3], 0.5) == 2
    assert saturation.percentile([1, 2, 3], 0.99) == 3
    assert saturation.percentile([7], 0.99) == 7
    assert saturation.percentile_rank(10, 0.5) == 4  # rank 5 of 10
    with pytest.raises(ValueError):
        saturation.percentile([], 0.5)


# ---------------------------------------------------------------------
# Phase reservoirs + saturation accumulators
# ---------------------------------------------------------------------
def test_phase_snapshot_percentiles():
    for ms in range(1, 101):
        saturation.observe_phase("dispatch.launch", ms / 1000.0)
    snap = saturation.phase_snapshot()["dispatch.launch"]
    assert snap["count"] == 100
    assert snap["n_samples"] == 100
    assert snap["p50_ms"] == pytest.approx(50.0)
    assert snap["p99_ms"] == pytest.approx(99.0)
    assert snap["max_ms"] == pytest.approx(100.0)
    assert snap["sum_ms"] == pytest.approx(5050.0)


def test_lane_util_and_busy_take_semantics():
    saturation.lane_util.add(1000, 1024)
    saturation.lane_util.add(200, 256)
    assert saturation.lane_util.take() == (1200, 1280, 2)
    assert saturation.lane_util.take() == (0, 0, 0)  # drained
    saturation.dispatcher_busy.add(0.5)
    busy, elapsed = saturation.dispatcher_busy.take()
    assert busy == pytest.approx(0.5)
    assert elapsed > 0


def test_queue_depth_snapshot():
    for d in range(1, 101):
        saturation.observe_queue_depth(d)
    snap = saturation.queue_depth_snapshot()
    assert snap["n_samples"] == 100
    assert snap["p50"] == 50
    assert snap["p99"] == 99
    assert snap["max"] == 100


# ---------------------------------------------------------------------
# SLO engine: burn-rate window math + fast-burn dump
# ---------------------------------------------------------------------
def test_slo_burn_rate_window_math():
    clock = [1000.0]
    slo = saturation.SloEngine(
        target_ms=100.0, objective=0.99, time_fn=lambda: clock[0]
    )
    # 100 requests in the current bucket: 2 bad -> bad fraction 0.02,
    # budget 0.01 -> burn 2.0 on every window containing the bucket.
    for i in range(100):
        good = slo.observe(0.05 if i >= 2 else 0.5)
        assert good is (i >= 2)
    assert slo.burn_rate(300) == pytest.approx(2.0)
    assert slo.burn_rate(3600) == pytest.approx(2.0)
    # 6 minutes later the 5m window has rolled past the counts; the 1h
    # window still sees them.
    clock[0] += 360.0
    assert slo.burn_rate(300) == 0.0
    assert slo.burn_rate(3600) == pytest.approx(2.0)
    # 61 minutes later everything expired.
    clock[0] += 3660.0
    assert slo.burn_rate(3600) == 0.0
    snap = slo.snapshot()
    assert snap["enabled"] is True
    assert snap["target_ms"] == 100.0


def test_slo_bucket_ring_reuse_zeroes_stale_slots():
    clock = [0.0]
    slo = saturation.SloEngine(100.0, 0.99, time_fn=lambda: clock[0])
    slo.observe(1.0)  # bad, bucket epoch 0
    # Exactly one ring revolution later the SAME slot is reused: the
    # stale count must not leak into the new epoch.
    clock[0] += slo.BUCKET_S * slo.N_BUCKETS
    slo.observe(0.01)  # good
    good, bad = slo._window_counts(clock[0], slo.BUCKET_S)
    assert (good, bad) == (1, 0)


def test_slo_disabled_is_inert():
    slo = saturation.SloEngine(target_ms=0.0)
    assert slo.observe(99.0) is None
    assert slo.burn_rate(300) == 0.0
    assert slo.snapshot() == {
        "enabled": False, "target_ms": 0.0, "objective": 0.99,
    }


def test_slo_fast_burn_trips_flight_recorder():
    clock = [50_000.0]
    slo = saturation.SloEngine(10.0, 0.999, time_fn=lambda: clock[0])
    # Below the volume floor nothing trips, no matter how bad: a lone
    # post-restart warmup request must not read as a page.
    for _ in range(saturation.SloEngine.FAST_MIN_TOTAL - 1):
        slo.observe(5.0)
        clock[0] += 0.05
    assert not [e for e in tracing.events_snapshot()
                if e["kind"] == "slo-fast-burn"]
    # Past the floor, all-bad traffic (burn = 1/0.001 = 1000 >> 14.4)
    # trips on the next check.
    for _ in range(20):
        slo.observe(5.0)
        clock[0] += 0.1
    events = [e for e in tracing.events_snapshot()
              if e["kind"] == "slo-fast-burn"]
    assert events, "fast burn did not trip the flight-recorder event"
    assert events[0]["burn_rate"] >= saturation.SloEngine.FAST_BURN
    # Rate-limited: a second trip inside TRIP_MIN_INTERVAL_S is absorbed.
    for _ in range(20):
        slo.observe(5.0)
        clock[0] += 0.1
    events = [e for e in tracing.events_snapshot()
              if e["kind"] == "slo-fast-burn"]
    assert len(events) == 1


def test_behavior_config_env_knobs():
    from gubernator_tpu.config import setup_daemon_config

    conf = setup_daemon_config(
        env={"GUBER_LATENCY_TARGET_MS": "250", "GUBER_SLO_OBJECTIVE": "0.999"},
    )
    assert conf.behaviors.latency_target_ms == 250.0
    assert conf.behaviors.slo_objective == 0.999
    with pytest.raises(ValueError):
        setup_daemon_config(env={"GUBER_SLO_OBJECTIVE": "99"})
    with pytest.raises(ValueError):
        setup_daemon_config(env={"GUBER_LATENCY_TARGET_MS": "fast"})


# ---------------------------------------------------------------------
# Hot-key sketch
# ---------------------------------------------------------------------
def test_hotkey_sketch_zipf_accuracy():
    rng = np.random.RandomState(7)
    n_keys, n_lanes = 2000, 40_000
    # Zipf-ish: ranks 0..9 soak most of the traffic.
    ranks = np.minimum(
        rng.zipf(1.3, size=n_lanes) - 1, n_keys - 1
    ).astype(np.int64)
    keys = [f"zipf:{r}" for r in range(n_keys)]
    true_counts = np.bincount(ranks, minlength=n_keys)
    sketch = saturation.HotKeySketch(width=4096, depth=4, topk=8)
    for lo in range(0, n_lanes, 1000):
        batch = ranks[lo:lo + 1000]
        batch_keys = [keys[r] for r in batch]
        hs = native.fnv1_batch(batch_keys) if native.available() else np.array(
            [hash(k) & 0xFFFFFFFFFFFFFFFF for k in batch_keys], np.uint64
        )
        sketch.update(hs, batch_keys)
    snap = sketch.snapshot()
    assert snap["total_lanes"] == n_lanes
    got = {row["key"]: row["estimate"] for row in snap["topk"]}
    true_top = np.argsort(true_counts)[::-1]
    # The heaviest keys must be in the top-K with count-min's one-sided
    # error: estimate >= truth, and within a small overcount.
    for r in true_top[:3]:
        key = keys[int(r)]
        assert key in got, (key, list(got)[:8])
        assert got[key] >= true_counts[r]
        assert got[key] <= true_counts[r] + n_lanes * 0.01


def test_hotkey_sketch_decay_halves():
    clock = [0.0]
    sk = saturation.HotKeySketch(
        width=256, depth=2, topk=4, decay_s=10.0, time_fn=lambda: clock[0]
    )
    hs = np.full(64, 12345, np.uint64)
    sk.update(hs, ["hot"] * 64)
    assert sk.snapshot()["topk"][0]["estimate"] == 64
    clock[0] = 11.0
    sk.update(np.array([999], np.uint64), ["cold"])
    est = {r["key"]: r["estimate"] for r in sk.snapshot()["topk"]}
    assert est["hot"] == 32  # halved by the decay


def test_hash_ring_feeds_sketch():
    from gubernator_tpu.parallel.hash_ring import ReplicatedConsistentHash

    ring = ReplicatedConsistentHash()
    ring.add("peer-a")
    ring.add("peer-b")
    sk = saturation.HotKeySketch(width=512, depth=2, topk=4)
    keys = ["viral"] * 50 + [f"cold{i}" for i in range(10)]
    codes, ids = ring.get_batch_codes(keys, sketch=sk)
    assert len(codes) == len(keys) and set(ids) == {"peer-a", "peer-b"}
    snap = sk.snapshot()
    assert snap["total_lanes"] == 60
    assert snap["topk"][0]["key"] == "viral"
    assert snap["topk"][0]["estimate"] >= 50


# ---------------------------------------------------------------------
# The native sketch fold against the numpy fold it replaced
# (tests/fold_reference.py), on the cells' own shapes
# ---------------------------------------------------------------------
def _take_hashes(names, uks):
    keys = native.PackedKeys(*native.pack_keys(
        [f"{a}_{b}" for a, b in zip(names, uks)]
    ))
    return native.fnv1_batch(keys), keys


def _assert_same_sketch(a, b):
    """Sketch `a` (the package's fold) and `b` (the reference's) after
    the same batches: the table cell for cell, and the top-K wherever
    estimates are distinct — entries AT a sketch's floor may be other
    keys of the same estimate (the two folds break ties their own
    way), every entry above both floors is the same key and count."""
    assert np.array_equal(a._tab, b._tab)
    assert (a.total_lanes, a.batches) == (b.total_lanes, b.batches)
    assert len(a._top) == len(b._top)
    if not a._top:
        return
    assert sorted(r[0] for r in a._top.values()) == sorted(
        r[0] for r in b._top.values())
    floor = min(r[0] for r in a._top.values())
    above_a = {h: r for h, r in a._top.items() if r[0] > floor}
    above_b = {h: r for h, r in b._top.items() if r[0] > floor}
    assert above_a == above_b


@pytest.mark.parametrize("shape", [
    "zipf-4096", "zipf-1028", "load-64", "coalesced", "hits-0-and-many",
])
def test_native_sketch_fold_is_the_numpy_fold(shape):
    from .fold_reference import ref_sketch_update, takes

    clock = [0.0]
    a, b = (
        saturation.HotKeySketch(width=4096, depth=4, topk=8, decay_s=10.0,
                                time_fn=lambda: clock[0])
        for _ in range(2)
    )
    a.update(np.zeros(0, np.uint64), [])  # an empty take folds nothing
    assert a.batches == 0 and not a._tab.any()
    for t, (names, uks, _hits) in enumerate(takes(shape)):
        if t == 3:
            clock[0] += 11.0  # a decay between two folds: both halve
        hs, keys = _take_hashes(names, uks)
        a.update(hs, keys)
        ref_sketch_update(b, hs, keys)
        _assert_same_sketch(a, b)
    assert a._last_decay == b._last_decay == 11.0
    assert a.batches == len(takes(shape))
    assert a.total_lanes == sum(len(t[0]) for t in takes(shape))
    assert 0 < a.candidates <= 8 * a.batches  # Python's share


def test_native_sketch_fold_distinct_estimates_same_topk():
    """Where no two estimates are equal the top-K is the reference's
    entry for entry: key i sent i + 1 times, through a list that fills,
    a cap (more candidates than K) and floors that rise."""
    from .fold_reference import ref_sketch_update

    a = saturation.HotKeySketch(width=8192, depth=4, topk=8)
    b = saturation.HotKeySketch(width=8192, depth=4, topk=8)
    rng = np.random.RandomState(4)
    for step in range(6):
        ids = np.repeat(np.arange(40), np.arange(40) + 1 + 50 * step)
        rng.shuffle(ids)
        keys = [f"rank:{i}" for i in ids]
        hs = native.fnv1_batch(keys)
        a.update(hs, keys)
        ref_sketch_update(b, hs, keys)
        assert np.array_equal(a._tab, b._tab)
        assert a._top == b._top
    assert {r[1] for r in a._top.values()} == {
        f"rank:{i}" for i in range(32, 40)}


def test_native_sketch_fold_odd_width_and_depth():
    """A width that is no power of two takes the modulo, not the mask:
    the same cells as numpy's."""
    from .fold_reference import ref_sketch_update

    a = saturation.HotKeySketch(width=1000, depth=3, topk=4)
    b = saturation.HotKeySketch(width=1000, depth=3, topk=4)
    rng = np.random.RandomState(8)
    for _ in range(5):
        keys = [f"odd:{i}" for i in rng.zipf(1.2, 900) % 5000]
        hs = native.fnv1_batch(keys)
        a.update(hs, keys)
        ref_sketch_update(b, hs, keys)
        _assert_same_sketch(a, b)


@pytest.mark.parametrize("shape", [
    "zipf-4096", "zipf-1028", "load-64", "coalesced", "hits-0-and-many",
])
def test_sketch_folds_the_same_without_the_native_build(shape, monkeypatch):
    """A host with no compiler (`native.available()` False) still
    constructs a sketch and folds into it: `native.cms_fold` answers
    from numpy there, the same answer position for position — tied
    candidates included — so the table AND the top-K come out equal."""
    from .fold_reference import takes

    a = saturation.HotKeySketch(width=4096, depth=4, topk=8)
    batches = [_take_hashes(n, u) for n, u, _ in takes(shape)]
    for hs, keys in batches:
        a.update(hs, keys)
    monkeypatch.setattr(native, "_get_lib", lambda: None)
    assert not native.available()
    b = saturation.HotKeySketch(width=4096, depth=4, topk=8)
    b.update(np.zeros(0, np.uint64), [])
    for hs, keys in batches:
        b.update(hs, keys)
    assert np.array_equal(a._tab, b._tab)
    assert a._top == b._top and list(a._top) == list(b._top)
    assert (a.batches, a.total_lanes, a.candidates) == (
        b.batches, b.total_lanes, b.candidates)


def test_cms_fold_numpy_twin_answers_position_for_position(monkeypatch):
    """`native.cms_fold` itself, both bodies: weights, tracked hashes in
    and out of the batch, a floor that cuts, more candidates than topk
    with tied estimates, an odd width, an empty batch."""
    rng = np.random.RandomState(11)
    salts = saturation._CMS_SALTS[:3]
    calls = []
    for n in (0, 1, 7, 900, 900):
        hs = native.fnv1_batch([f"c:{i}" for i in rng.zipf(1.3, n) % 400])
        w = rng.randint(0, 4, n).astype(np.int64) if n != 7 else None
        tracked = np.concatenate([hs[:5], np.arange(3, dtype=np.uint64)])
        calls.append((hs, w, tracked, int(rng.randint(-1, 3)), 6))
    outs = []
    for lib_off in (False, True):
        if lib_off:
            monkeypatch.setattr(native, "_get_lib", lambda: None)
        tab = np.zeros((3, 1000), dtype=np.int64)
        outs.append([
            [np.array(x) for x in native.cms_fold(tab, salts, *c)] + [
                tab.copy()]
            for c in calls
        ])
    for got, want in zip(*outs):
        for x, y in zip(got, want):
            assert x.dtype == y.dtype and np.array_equal(x, y)
    assert outs[0][-1][-1].any()


def test_native_sketch_fold_threads_lose_no_count():
    """Two threads folding into one sketch: the lock still covers the
    native call, so no add is lost (every cell is the serial sum)."""
    from .fold_reference import takes

    batches = [_take_hashes(n, u) for n, u, _ in takes("zipf-1028")]
    shared = saturation.HotKeySketch(width=256, depth=4, topk=8,
                                     decay_s=1e9)
    serial = saturation.HotKeySketch(width=256, depth=4, topk=8,
                                     decay_s=1e9)
    reps = 40

    def run():
        for _ in range(reps):
            for hs, keys in batches:
                shared.update(hs, keys)

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
        for hs, keys in batches:
            serial.update(hs, keys)
    assert np.array_equal(shared._tab, serial._tab)
    assert shared._tab[0].sum() == 2 * reps * sum(len(h) for h, _ in batches)
    assert shared.total_lanes == serial.total_lanes


# ---------------------------------------------------------------------
# Occupancy telemetry vs oracle + the zero-extra-dispatch pin
# ---------------------------------------------------------------------
@pytest.mark.skipif(not native.available(), reason="native runtime unavailable")
def test_occupancy_and_evictions_vs_oracle():
    cap = 64
    store = one_device_store(cap)
    n_batches, per_batch = 3, 64

    def batch(salt):
        keys = [f"ev{salt}:{i}" for i in range(per_batch)]
        z = np.zeros(per_batch, np.int32)
        return keys, z, z.copy()

    for b in range(n_batches):
        keys, algo, beh = batch(b)
        store.apply_columns(
            keys, algo, beh,
            np.ones(per_batch, np.int64),
            np.full(per_batch, 1_000, np.int64),
            np.full(per_batch, 3_600_000, np.int64),
            T0 + b,
        )
    # Oracle: 192 distinct keys through a 64-slot LRU = first batch
    # fills, each later distinct key evicts exactly one.
    assert store.size() == cap
    expected_evictions = n_batches * per_batch - cap
    assert store.tables[0].evictions == expected_evictions

    # ZERO-extra-dispatch pin (the replica_commit_dispatches playbook):
    # scraping occupancy/saturation and serving /debug/status must not
    # launch device programs — counted, not timed.
    svc = _service()
    try:
        before = store.device_dispatches
        assert before >= n_batches  # the traffic itself dispatched
        m = Metrics()
        m.slo = saturation.SloEngine(100.0)

        class _Wrap:
            store = None
            conf = svc.conf
            columnar_batcher = svc.columnar_batcher
            local_batcher = svc.local_batcher
            hotkeys = svc.hotkeys

            def ingress_queued_lanes(self):
                return 0

        w = _Wrap()
        w.store = store
        for _ in range(5):
            m.observe_saturation(w)
        assert store.device_dispatches == before
        # The service's own debug surface over its mesh store: same pin.
        svc.get_rate_limits_columns(_cols(32))
        sd = svc.store.device_dispatches
        rd = getattr(svc.store, "replica_commit_dispatches", 0)
        for _ in range(5):
            svc.debug_status()
            svc.metrics.observe_saturation(svc)
        assert svc.store.device_dispatches == sd
        assert getattr(svc.store, "replica_commit_dispatches", 0) == rd
        # And the gauges reflect the oracle numbers.
        ev = m.occupancy_evictions.labels(shard="0")._value.get()  # noqa: SLF001
        assert ev == expected_evictions
    finally:
        svc.close()


# ---------------------------------------------------------------------
# /debug endpoints on both gateways
# ---------------------------------------------------------------------
def _check_debug_payloads(get):
    status = json.loads(get("/debug/status"))
    assert status["health"]["status"] == "healthy"
    assert status["version"]
    assert status["occupancy"]["capacity"] > 0
    assert status["occupancy"]["used"] >= 1
    if native.available():  # the native table's key index, a shard
        for row in status["occupancy"]["shards"]:
            assert row["index"]["probes"] >= row["index"]["lookups"] >= 0 and row["index"]["refused"] == 0
    assert "queuedLanes" in status["ingress"]
    assert "slo" in status and "hotkeys" in status
    latency = json.loads(get("/debug/latency"))
    assert "dispatch.launch" in latency["phases"]
    assert latency["phases"]["dispatch.launch"]["count"] >= 1
    assert "ingress.total" in latency["phases"]
    assert "slo" in latency
    hot = json.loads(get("/debug/hotkeys"))
    assert {"topk", "total_lanes", "width", "depth"} <= set(hot)


def test_debug_endpoints_handle_request():
    svc = _service()
    try:
        body = json.dumps({"requests": [
            {"name": "obs", "uniqueKey": f"k{i}", "hits": "1",
             "limit": "100", "duration": "60000"} for i in range(8)
        ]}).encode()
        st, _, _ = handle_request(svc, "POST", "/v1/GetRateLimits", body)
        assert st == 200

        def get(path):
            st, ctype, payload = handle_request(svc, "GET", path, b"")
            assert st == 200, (path, payload)
            assert ctype == "application/json"
            return payload

        _check_debug_payloads(get)
        # The scrape carries the new families.
        st, _, metrics = handle_request(svc, "GET", "/metrics", b"")
        text = metrics.decode()
        for fam in ("gubernator_latency_attribution_seconds",
                    "gubernator_occupancy_slots",
                    "gubernator_slo_burn_rate",
                    "gubernator_dispatcher_busy_ratio"):
            assert fam in text, fam
    finally:
        svc.close()


def test_debug_endpoints_sync_gateway():
    import urllib.request

    svc = _service()
    gw = GatewayServer(svc)
    gw.start()
    try:
        req = urllib.request.Request(
            f"http://{gw.address}/v1/GetRateLimits",
            data=json.dumps({"requests": [
                {"name": "obs", "uniqueKey": f"g{i}", "hits": "1",
                 "limit": "10", "duration": "60000"} for i in range(8)
            ]}).encode(),
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200

        def get(path):
            with urllib.request.urlopen(
                f"http://{gw.address}{path}", timeout=30
            ) as r:
                assert r.status == 200
                return r.read()

        _check_debug_payloads(get)
    finally:
        gw.close()
        svc.close()


@pytest.mark.skipif(not native.available(), reason="native runtime unavailable")
def test_debug_endpoints_native_gateway():
    import urllib.request

    from gubernator_tpu.gateway import NativeGatewayServer

    svc = _service()
    gw = NativeGatewayServer(svc, "127.0.0.1:0")
    gw.start()
    try:
        req = urllib.request.Request(
            f"http://{gw.address}/v1/GetRateLimits",
            data=json.dumps({"requests": [
                {"name": "obs", "uniqueKey": f"n{i}", "hits": "1",
                 "limit": "10", "duration": "60000"} for i in range(8)
            ]}).encode(),
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200

        def get(path):
            with urllib.request.urlopen(
                f"http://{gw.address}{path}", timeout=30
            ) as r:
                assert r.status == 200
                return r.read()

        _check_debug_payloads(get)
    finally:
        gw.close()
        svc.close()


# ---------------------------------------------------------------------
# SLO + attribution wired through the request path
# ---------------------------------------------------------------------
def test_observe_latency_feeds_slo_and_total_phase():
    m = Metrics()
    m.slo = saturation.SloEngine(target_ms=100.0, objective=0.9)
    m.observe_latency("/pb.gubernator.V1/GetRateLimits", 0.05)   # good
    m.observe_latency("/pb.gubernator.V1/GetRateLimits", 0.5)    # bad
    m.observe_latency("/pb.gubernator.V1/HealthCheck", 9.9)      # ignored
    snap = m.slo.snapshot()
    assert snap["good_5m"] == 1 and snap["bad_5m"] == 1
    phases = saturation.phase_snapshot()
    assert phases["ingress.total"]["count"] == 2
    good = m.slo_requests.labels(verdict="good")._value.get()  # noqa: SLF001
    bad = m.slo_requests.labels(verdict="bad")._value.get()  # noqa: SLF001
    assert (good, bad) == (1, 2 - 1)


def test_service_latency_target_from_behaviors():
    from gubernator_tpu.config import BehaviorConfig

    beh = BehaviorConfig(latency_target_ms=150.0, slo_objective=0.95)
    svc = V1Service(ServiceConfig(cache_size=256, behaviors=beh))
    try:
        assert svc.slo.enabled and svc.slo.target_ms == 150.0
        assert svc.metrics.slo is svc.slo
        assert svc.slo.objective == 0.95
    finally:
        svc.close()


# ---------------------------------------------------------------------
# Wire parity: the plane must not touch a single wire byte at sample 0
# ---------------------------------------------------------------------
def test_sample0_wire_identical_with_plane_active():
    cols = (
        ["obs"] * 4,
        [f"w{i}" for i in range(4)],
        np.zeros(4, np.int32),
        np.zeros(4, np.int32),
        np.ones(4, np.int64),
        np.full(4, 100, np.int64),
        np.full(4, 60_000, np.int64),
    )
    assert tracing.sample_rate() == 0.0
    before = wire.encode_columns_frame(cols)
    # Exercise every always-on surface: attribution, SLO (enabled and
    # burning), the sketch, queue-depth samples, and a live request.
    svc = _service()
    try:
        svc.slo.target_ms, svc.slo.enabled = 1e-9, True
        svc.get_rate_limits_columns(_cols(16))
        saturation.observe_phase("peer.rpc", 0.001)
        saturation.observe_queue_depth(5)
        svc.hotkeys.update(np.array([1, 2, 3], np.uint64), ["a", "b", "c"])
        handle_request(svc, "GET", "/metrics", b"")
        handle_request(svc, "GET", "/debug/status", b"")
    finally:
        svc.close()
    after = wire.encode_columns_frame(cols)
    assert before == after  # byte-identical: no trace/telemetry bytes
