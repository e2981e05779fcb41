"""Federation plane tests (federation.py): the per-region accumulator,
encode-once fan-out, carry/requeue partition semantics, the
region_conservation audit chain, and mixed-version interop.

Two tiers:

* unit tests against a FakeService — deterministic, no device, no
  sockets: batching semantics (multi_region_batch_limit honored, per-key
  aggregation), the PR 5 hit-carry discipline per destination region
  (provably-unapplied requeues, timeout-shaped drops counted, bounded
  carry, departed regions), and the encode-once sharing rule;
* cluster tests against real daemons — the columnar wire end-to-end,
  a seeded FaultPlan DUPLICATE on the region wire proven caught by
  `region_conservation`, the chaos-safe carry/requeue exactly-once
  regression, and both interop directions.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np
import pytest

from gubernator_tpu import audit, faults, federation
from gubernator_tpu.cluster import fast_test_behaviors
from gubernator_tpu.config import BehaviorConfig, DaemonConfig
from gubernator_tpu.daemon import Daemon
from gubernator_tpu.federation import FederationManager, RegionBatch
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.parallel.region import RegionPicker
from gubernator_tpu.peer_client import PeerError
from gubernator_tpu.types import (
    Behavior,
    GetRateLimitsRequest,
    PeerInfo,
    RateLimitRequest,
)
from gubernator_tpu.utils.clock import Clock


# ----------------------------------------------------------------------
# Unit tier: FakeService drives the manager deterministically
# ----------------------------------------------------------------------
class FakePeer:
    """Region-owner stand-in recording update_region_columns sends; a
    script of exceptions makes it misbehave first."""

    def __init__(self, addr: str, dc: str, script=()):
        self.info = PeerInfo(
            grpc_address=addr, http_address=f"h-{addr}", data_center=dc
        )
        self.batches = []
        self.script = list(script)

    def update_region_columns(self, batch, timeout_s=None, trace_ctx=None):
        if self.script:
            raise self.script.pop(0)
        self.batches.append(batch)


class FakeService:
    def __init__(self, peers, data_center="dc-a", batch_limit=1000,
                 sync_wait_s=3600.0):
        beh = BehaviorConfig(
            multi_region_sync_wait_s=sync_wait_s,
            multi_region_batch_limit=batch_limit,
            multi_region_timeout_s=5.0,
        )
        self.conf = SimpleNamespace(behaviors=beh, data_center=data_center)
        self.metrics = Metrics()
        self._rp = RegionPicker()
        for p in peers:
            self._rp.add(p)

    def get_region_picker(self):
        return self._rp

    def _peer_send_ex(self, op, fn):
        try:
            fn()
            return True, None
        except Exception as e:  # noqa: BLE001 — shape-classified by caller
            return False, e


def mr_req(key, hits=1, limit=1000):
    return RateLimitRequest(
        name="mr", unique_key=key, hits=hits, limit=limit, duration=60_000,
        behavior=int(Behavior.MULTI_REGION),
    )


@pytest.fixture
def ledger():
    before = audit.ledger_snapshot()

    def delta(counter):
        return audit.ledger_snapshot()[counter] - before[counter]

    return delta


def make_mgr(peers, **kw):
    svc = FakeService(peers, **kw)
    mgr = FederationManager(svc)
    return svc, mgr


def test_per_key_aggregation_and_flush(ledger):
    peer = FakePeer("b:81", "dc-b")
    svc, mgr = make_mgr([peer])
    try:
        for _ in range(3):
            mgr.queue_hits(mr_req("a", hits=2))
        mgr.queue_hits(mr_req("b", hits=1))
        assert mgr.run_once() is True
        (batch,) = peer.batches
        assert sorted(
            zip(batch.cols.unique_keys, batch.cols.hits.tolist())
        ) == [("a", 6), ("b", 1)]
        # MULTI_REGION stripped on the wire (the no-amplification rule)
        assert not (
            batch.cols.behavior & int(Behavior.MULTI_REGION)
        ).any()
        assert batch.cols.origin == "dc-a"
        assert ledger("region_agg_hits") == 7
        assert ledger("region_sent_hits") == 7
        # idle flush is a no-op
        assert mgr.run_once() is False
    finally:
        mgr.stop()


def test_batch_limit_kicks_early_flush():
    """multi_region_batch_limit was parsed-but-unenforced before the
    federation plane: reaching it must flush WITHOUT waiting out the
    3600s window (the reference's queue-full flush)."""
    peer = FakePeer("b:81", "dc-b")
    svc, mgr = make_mgr([peer], batch_limit=3)
    try:
        for i in range(3):
            mgr.queue_hits(mr_req(f"k{i}"))
        deadline = time.time() + 5.0
        while time.time() < deadline and not peer.batches:
            time.sleep(0.01)
        assert peer.batches, "batch-limit flush never kicked"
        assert len(peer.batches[0]) == 3
    finally:
        mgr.stop()


def test_encode_once_across_regions():
    """When every region's ring maps the whole flush to one owner, all
    regions share the SAME RegionBatch object — the frame/proto bytes
    encode once per flush, not once per region."""
    pb_ = FakePeer("b:81", "dc-b")
    pc_ = FakePeer("c:81", "dc-c")
    svc, mgr = make_mgr([pb_, pc_])
    try:
        mgr.queue_hits(mr_req("a", hits=2))
        assert mgr.run_once()
        assert pb_.batches and pc_.batches
        assert pb_.batches[0] is pc_.batches[0]
    finally:
        mgr.stop()


def test_provably_unapplied_requeues_then_delivers_once(ledger):
    """The PR 5 hit-carry discipline per destination region: a breaker
    fast-fail / connection-level not-ready provably never applied, so
    the hits carry into the next flush (summed per key) and deliver
    exactly once after heal."""
    peer = FakePeer(
        "b:81", "dc-b",
        script=[PeerError("injected", not_ready=True)],
    )
    svc, mgr = make_mgr([peer])
    try:
        mgr.queue_hits(mr_req("a", hits=3))
        assert mgr.run_once()
        assert peer.batches == []
        assert mgr.snapshot()["carryKeyTotal"] == 1
        assert ledger("region_sent_hits") == 0
        # next window adds 2 more hits for the same key
        mgr.queue_hits(mr_req("a", hits=2))
        assert mgr.run_once()
        (batch,) = peer.batches
        assert batch.cols.unique_keys == ["a"]
        assert batch.cols.hits.tolist() == [5]  # carried 3 + new 2
        assert mgr.snapshot()["carryKeyTotal"] == 0
        assert ledger("region_sent_hits") == 5
        assert ledger("region_agg_hits") == 5
        assert ledger("region_dropped_hits") == 0
    finally:
        mgr.stop()


def test_timeout_shaped_failure_drops_counted(ledger):
    """A timeout may have applied remotely: re-sending would
    double-count, so the hits drop COUNTED instead of requeueing."""
    peer = FakePeer(
        "b:81", "dc-b",
        script=[PeerError("deadline", not_ready=False)],
    )
    svc, mgr = make_mgr([peer])
    try:
        mgr.queue_hits(mr_req("a", hits=4))
        assert mgr.run_once()
        assert mgr.snapshot()["carryKeyTotal"] == 0
        assert mgr.snapshot()["droppedHits"] == 4
        assert ledger("region_dropped_hits") == 4
        # delivery inequality stays one-sided: sent + dropped <= agg
        assert ledger("region_sent_hits") == 0
        assert ledger("region_agg_hits") == 4
    finally:
        mgr.stop()


def test_carry_is_bounded_and_overflow_drops_counted(ledger, monkeypatch):
    monkeypatch.setattr(federation, "REGION_CARRY_MAX", 2)
    peer = FakePeer(
        "b:81", "dc-b",
        script=[PeerError("injected", not_ready=True)],
    )
    svc, mgr = make_mgr([peer])
    try:
        for i in range(4):
            mgr.queue_hits(mr_req(f"k{i}", hits=1))
        assert mgr.run_once()
        snap = mgr.snapshot()
        assert snap["carryKeyTotal"] == 2  # capped
        assert snap["droppedHits"] == 2   # overflow counted, not lost
        assert ledger("region_dropped_hits") == 2
        # the audited gauge reflects the live carry for region_slack
        assert audit.gauges_snapshot()[audit.REGION_CARRY_GAUGE] == 2
    finally:
        mgr.stop()


def test_departed_region_carry_drops_counted(ledger):
    peer = FakePeer(
        "b:81", "dc-b",
        script=[PeerError("injected", not_ready=True)],
    )
    svc, mgr = make_mgr([peer])
    try:
        mgr.queue_hits(mr_req("a", hits=3))
        assert mgr.run_once()
        assert mgr.snapshot()["carryKeyTotal"] == 1
        # dc-b leaves the membership entirely
        svc._rp.remove(peer)
        mgr.run_once()
        assert mgr.snapshot()["carryKeyTotal"] == 0
        assert ledger("region_dropped_hits") == 3
    finally:
        mgr.stop()


def test_unset_data_center_single_region_is_a_noop(ledger):
    """A GUBER_DATA_CENTER-unset daemon with no named-region peers must
    behave exactly like the pre-PR build: MULTI_REGION hits apply
    locally, the queue drains without sends, and NO region ledger
    counters move."""
    svc, mgr = make_mgr([], data_center="")
    try:
        mgr.queue_hits(mr_req("a", hits=3))
        assert mgr.run_once() is False
        for c in ("region_agg_hits", "region_sent_hits",
                  "region_dropped_hits", "region_admitted_hits",
                  "region_wire_hits"):
            assert ledger(c) == 0, c
        assert mgr.snapshot()["flushes"] == 0
    finally:
        mgr.stop()


def test_unroutable_keys_requeue(ledger):
    """A region ring that churns mid-flush (pick answers None) is a
    provably-unapplied outcome: the keys carry instead of dropping."""
    peer = FakePeer("b:81", "dc-b")
    svc, mgr = make_mgr([peer])
    try:
        mgr.queue_hits(mr_req("a", hits=2))

        real_pick = svc._rp.pick
        svc._rp.pick = lambda dc, k: None
        assert mgr.run_once() is False  # nothing routable
        assert mgr.snapshot()["carryKeyTotal"] == 1
        svc._rp.pick = real_pick
        assert mgr.run_once()
        (batch,) = peer.batches
        assert batch.cols.hits.tolist() == [2]
        assert ledger("region_sent_hits") == 2
    finally:
        mgr.stop()


# ----------------------------------------------------------------------
# Cluster tier: real daemons, real wire
# ----------------------------------------------------------------------
T0 = 1_700_000_000_000


def _regional_daemon(dc, clock, region_columns=True, sync_wait_s=3600.0):
    behaviors = fast_test_behaviors()
    behaviors.multi_region_sync_wait_s = sync_wait_s
    behaviors.global_sync_wait_s = 3600.0
    behaviors.region_columns = region_columns
    return Daemon(
        DaemonConfig(
            listen_address="127.0.0.1:0",
            grpc_listen_address="127.0.0.1:0",
            cache_size=4096,
            global_cache_size=256,
            data_center=dc,
            behaviors=behaviors,
            peer_discovery_type="static",
        ),
        clock=clock,
    ).start()


@pytest.fixture
def two_region_pair(request):
    """One daemon per region, manual flush control (3600s window)."""
    marker = request.node.get_closest_marker("region_pair")
    kwargs = dict(marker.kwargs) if marker else {}
    clock = Clock()
    clock.freeze(T0)
    a = _regional_daemon("dc-a", clock, **kwargs.get("a", {}))
    b = _regional_daemon("dc-b", clock, **kwargs.get("b", {}))
    peers = [a.peer_info, b.peer_info]
    a.set_peers(peers)
    b.set_peers(peers)
    yield a, b
    a.close()
    b.close()


def _remaining_on(daemon, name, key, limit=1000):
    resp = daemon.service.get_peer_rate_limits(
        GetRateLimitsRequest(requests=[
            RateLimitRequest(name=name, unique_key=key, hits=0, limit=limit,
                             duration=60_000)
        ])
    )
    assert resp.responses[0].error == ""
    return resp.responses[0].remaining


def _region_client(daemon, dc, hash_key):
    client = daemon.service.get_region_picker().pick(dc, hash_key)
    assert client is not None
    return client


def test_columnar_wire_end_to_end(two_region_pair):
    a, b = two_region_pair
    a.service.get_rate_limits(GetRateLimitsRequest(requests=[
        RateLimitRequest(name="mr", unique_key="e2e", hits=5, limit=1000,
                         duration=60_000,
                         behavior=int(Behavior.MULTI_REGION))
    ]))
    before = audit.ledger_snapshot()
    assert a.service.multi_region_mgr.run_once()
    after = audit.ledger_snapshot()
    # negotiated columnar, not the classic fallback
    client = _region_client(a, "dc-b", "mr_e2e")
    assert client._region_columnar is True
    assert _remaining_on(b, "mr", "e2e") == 995
    # sender chain: admitted == wire == sent == 5; receiver chain:
    # recv == applied == 5 (the shared in-process ledger sees both)
    for c in ("region_admitted_hits", "region_wire_hits",
              "region_sent_hits", "region_recv_hits",
              "region_applied_hits"):
        assert after[c] - before[c] == 5, c
    # audits on both sides stay silent
    for d in two_region_pair:
        d.service.auditor.check_now()
        assert d.service.auditor.snapshot()["violationTotal"] == 0
    # debug surface carries the region section
    status = a.service.debug_status()["region"]
    assert status["dataCenter"] == "dc-a"
    assert status["regions"] == {"dc-b": {"peers": 1, "breakerOpen": 0}}
    assert status["sentHits"] == 5


@pytest.mark.chaos
def test_seeded_duplicate_on_region_wire_is_caught(two_region_pair):
    """Acceptance line: a FaultPlan DUPLICATE on the region wire — the
    byzantine re-delivery of an applied batch — must double
    region_wire_hits against a single region_admitted_hits note and
    trip region_conservation on the audit."""
    a, b = two_region_pair
    # burn the auditor's silent seeding pass so the next check can fire
    a.service.auditor.check_now()
    plan = faults.FaultPlan(seed=17)
    plan.duplicate(op="UpdateRegionColumns")
    faults.install(plan)
    try:
        a.service.get_rate_limits(GetRateLimitsRequest(requests=[
            RateLimitRequest(name="mr", unique_key="dup", hits=4, limit=1000,
                             duration=60_000,
                             behavior=int(Behavior.MULTI_REGION))
        ]))
        before = audit.ledger_snapshot()
        assert a.service.multi_region_mgr.run_once()
        after = audit.ledger_snapshot()
        assert after["region_admitted_hits"] - before["region_admitted_hits"] == 4
        assert after["region_wire_hits"] - before["region_wire_hits"] == 8
        a.service.auditor.check_now()
        snap = a.service.auditor.snapshot()
        assert snap["violations"].get("region_conservation", 0) >= 1
    finally:
        faults.uninstall()


@pytest.mark.chaos
def test_chaos_carry_requeues_and_delivers_exactly_once(two_region_pair):
    """The carry/requeue regression: a partition toward the remote
    region carries the flush; heal delivers the carried hits EXACTLY
    once (remote remaining moves by the summed hits, audits silent)."""
    a, b = two_region_pair
    plan = faults.FaultPlan(seed=23)
    rule = plan.partition(b.peer_info.grpc_address,
                          op="UpdateRegionColumns")
    faults.install(plan)
    try:
        a.service.get_rate_limits(GetRateLimitsRequest(requests=[
            RateLimitRequest(name="mr", unique_key="carry", hits=3,
                             limit=1000, duration=60_000,
                             behavior=int(Behavior.MULTI_REGION))
        ]))
        a.service.multi_region_mgr.run_once()
        assert a.service.multi_region_mgr.snapshot()["carryKeyTotal"] == 1
        assert _remaining_on(b, "mr", "carry") == 1000  # nothing landed
        # second window queues 2 more hits while partitioned
        a.service.get_rate_limits(GetRateLimitsRequest(requests=[
            RateLimitRequest(name="mr", unique_key="carry", hits=2,
                             limit=1000, duration=60_000,
                             behavior=int(Behavior.MULTI_REGION))
        ]))
        plan.heal(rule.peer)
        assert a.service.multi_region_mgr.run_once()
        assert _remaining_on(b, "mr", "carry") == 995  # 3+2, exactly once
        assert a.service.multi_region_mgr.snapshot()["carryKeyTotal"] == 0
        for d in two_region_pair:
            d.service.auditor.check_now()
            assert d.service.auditor.snapshot()["violationTotal"] == 0
    finally:
        faults.uninstall()


@pytest.mark.region_pair(b={"region_columns": False})
def test_interop_columnar_sender_classic_receiver(two_region_pair):
    """Downgrade direction: the receiver predates the plane (or runs
    GUBER_REGION_COLUMNS=0) — UNIMPLEMENTED/404 on the probe, sticky
    classic per-item fallback inside the same guarded call,
    breaker/health-neutral, hits still land exactly once."""
    a, b = two_region_pair
    a.service.get_rate_limits(GetRateLimitsRequest(requests=[
        RateLimitRequest(name="mr", unique_key="iop", hits=4, limit=1000,
                         duration=60_000,
                         behavior=int(Behavior.MULTI_REGION))
    ]))
    assert a.service.multi_region_mgr.run_once()
    client = _region_client(a, "dc-b", "mr_iop")
    assert client._region_columnar is False  # remembered per client
    assert _remaining_on(b, "mr", "iop") == 996
    assert not client.breaker.is_open
    assert a.service.health_check().status == "healthy"
    # sticky: the next flush goes straight to classic, still lands
    a.service.get_rate_limits(GetRateLimitsRequest(requests=[
        RateLimitRequest(name="mr", unique_key="iop", hits=1, limit=1000,
                         duration=60_000,
                         behavior=int(Behavior.MULTI_REGION))
    ]))
    assert a.service.multi_region_mgr.run_once()
    assert _remaining_on(b, "mr", "iop") == 995
    for d in two_region_pair:
        d.service.auditor.check_now()
        assert d.service.auditor.snapshot()["violationTotal"] == 0


@pytest.mark.region_pair(a={"region_columns": False})
def test_interop_classic_sender_columnar_receiver(two_region_pair):
    """Upgrade direction: a classic sender (pre-federation wire) talks
    to a columnar receiver through the ordinary GetPeerRateLimits door
    — behavior-identical application, no region receive counters."""
    a, b = two_region_pair
    before = audit.ledger_snapshot()
    a.service.get_rate_limits(GetRateLimitsRequest(requests=[
        RateLimitRequest(name="mr", unique_key="up", hits=2, limit=1000,
                         duration=60_000,
                         behavior=int(Behavior.MULTI_REGION))
    ]))
    assert a.service.multi_region_mgr.run_once()
    client = _region_client(a, "dc-b", "mr_up")
    assert client._region_columnar is False  # knob-off: never probes
    assert _remaining_on(b, "mr", "up") == 998
    after = audit.ledger_snapshot()
    # classic wire enters the receiver through the peer door, not the
    # region columnar surface
    assert after["region_recv_hits"] == before["region_recv_hits"]
    assert after["region_sent_hits"] - before["region_sent_hits"] == 2
    for d in two_region_pair:
        d.service.auditor.check_now()
        assert d.service.auditor.snapshot()["violationTotal"] == 0
