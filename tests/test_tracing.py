"""End-to-end request tracing + flight recorder (tracing.py).

Covers the PR's acceptance legs:

* one trace id across the columnar peer hop — ingress, batch-window,
  all five pipeline-stage spans and the peer RPC span surface in
  /debug/traces, queried over both daemons' gateways;
* GUBER_TRACE_SAMPLE=0 wire parity — frame bytes and proto-columns
  bytes are identical to the pre-trace encodings in both directions,
  and peers ignore/renegotiate the trace column cleanly;
* the flight recorder's ring ordering, event auto-dump triggers, and
  the no-op fast path;
* satellites: trace ids on structured log records, the build-info
  gauge + /healthz version, and the concurrent-scrape guarantee for
  take_pipeline_stats-backed gauges.
"""

import http.client
import io
import json
import logging
import struct
import threading

import numpy as np
import pytest

from gubernator_tpu import tracing, wire
from gubernator_tpu import __version__
from gubernator_tpu.cluster import fast_test_behaviors
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.daemon import Daemon
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.peer_client import PeerClient, PeerError
from gubernator_tpu.proto import peers_columns_pb2 as pc_pb
from gubernator_tpu.types import PeerInfo, SECOND
from gubernator_tpu.utils.clock import Clock
from gubernator_tpu.utils.logging import category_logger, setup_logging

T0 = 1_573_430_430_000


@pytest.fixture
def sampled():
    """Tracing at sample rate 1.0 with clean rings; always restored."""
    tracing.reset()
    prev = tracing.sample_rate()
    tracing.set_sample_rate(1.0)
    yield
    tracing.set_sample_rate(prev)
    tracing.reset()


# ----------------------------------------------------------------------
# W3C traceparent + span primitives
# ----------------------------------------------------------------------
def test_traceparent_round_trip():
    ctx = tracing.SpanContext(0xABCDEF, 0x1234)
    tp = tracing.format_traceparent(ctx)
    assert tp == f"00-{0xABCDEF:032x}-{0x1234:016x}-01"
    assert tracing.parse_traceparent(tp) == (0xABCDEF, 0x1234, True)
    # sampled flag clear
    assert tracing.parse_traceparent(tp[:-2] + "00")[2] is False


@pytest.mark.parametrize(
    "bad",
    ["", "garbage", "00-zz-1-01", "00-" + "0" * 32 + "-" + "0" * 16 + "-01",
     "ff-" + "a" * 32 + "-" + "b" * 16 + "-01", "00-abc-def-01"],
)
def test_traceparent_malformed(bad):
    assert tracing.parse_traceparent(bad) is None


def test_disabled_is_noop_singleton():
    tracing.reset()
    prev = tracing.sample_rate()
    tracing.set_sample_rate(0.0)
    try:
        a = tracing.ingress_span("http", "/x")
        b = tracing.ingress_span("grpc", "/y")
        assert a is b and not a  # shared no-op, falsy
        with a:
            assert tracing.current() is None
        assert tracing.spans_snapshot() == []
        assert tracing.new_batch([tracing.SpanContext(1, 2)]) is None
    finally:
        tracing.set_sample_rate(prev)


def test_sampled_span_links_and_filter(sampled):
    with tracing.ingress_span("http", "/v1/GetRateLimits") as sp:
        lane_ctx = tracing.current()
        assert lane_ctx is sp.ctx
    bt = tracing.new_batch([lane_ctx])
    tracing.stage_span("dispatch.prepare", 0.001, bt, lanes=4)
    spans = tracing.spans_snapshot(lane_ctx.trace_hex)
    names = {s["name"] for s in spans}
    # dispatch.prepare matches via its LINK, not its own trace id
    assert names == {"ingress.http", "dispatch.prepare"}
    prep = next(s for s in spans if s["name"] == "dispatch.prepare")
    assert prep["trace_id"] == bt.ctx.trace_hex != lane_ctx.trace_hex
    assert prep["links"][0]["trace_id"] == lane_ctx.trace_hex
    assert prep["attrs"]["lanes"] == 4


def test_local_rate_decides_not_the_upstream_flag(sampled):
    """The traceparent contributes ids; its sampled flag neither
    forces nor suppresses — untrusted callers must not control the
    sampling rate in either direction."""
    # flag 00 at local rate 1.0: still traced, trace id adopted
    tp = f"00-{'a' * 32}-{'b' * 16}-00"
    sp = tracing.ingress_span("http", "/x", tp)
    assert sp and sp.ctx.trace_hex == "a" * 32
    # flag 01 at local rate 0: stays dark — no forced sampling
    tracing.set_sample_rate(0.0)
    assert not tracing.ingress_span("http", "/x", tp[:-2] + "01")


def test_ring_wraps_in_order(sampled):
    ring = tracing._Ring(8)
    for i in range(20):
        ring.record({"i": i})
    got = [r["i"] for r in ring.snapshot()]
    assert got == list(range(12, 20))


def test_event_auto_dump_and_snapshot(sampled):
    tracing.record_event("shed", lanes=5, queued=10, cap=8)
    evs = tracing.events_snapshot()
    assert evs and evs[-1]["kind"] == "shed" and evs[-1]["lanes"] == 5


# ----------------------------------------------------------------------
# Wire parity: GUBER_TRACE_SAMPLE=0 is byte-identical, trace column
# decodes, classic peers ignore it
# ----------------------------------------------------------------------
def _cols(n=1):
    return (
        [f"n{i}" for i in range(n)],
        [f"k{i}" for i in range(n)],
        np.zeros(n, np.int32),
        np.zeros(n, np.int32),
        np.ones(n, np.int64),
        np.full(n, 10, np.int64),
        np.full(n, 9 * SECOND, np.int64),
    )


def test_frame_trace_trailer_golden():
    cols = _cols(1)
    plain = wire.encode_columns_frame(cols)
    traced = wire.encode_columns_frame(cols, trace=[(0, 1, 0xAB, 0xCD)])
    # sample-0 parity: no trace -> exact pre-trace bytes
    assert wire.encode_columns_frame(cols, trace=None) == plain
    assert wire.encode_columns_frame(cols, trace=[]) == plain
    # the trailer is strictly appended, pinned byte-for-byte
    expected_trailer = (
        b"GTRC"
        + (1).to_bytes(4, "little")
        + (0).to_bytes(4, "little") + (1).to_bytes(4, "little")
        + (0xAB).to_bytes(16, "big")
        + (0xCD).to_bytes(8, "big")
    )
    assert traced == plain + expected_trailer
    got = wire.decode_columns_frame(traced)
    assert got.trace_ctx == [(0, 1, 0xAB, 0xCD)]
    assert wire.decode_columns_frame(plain).trace_ctx is None


def test_frame_garbage_trailer_still_rejected():
    frame = wire.encode_columns_frame(_cols(1))
    with pytest.raises(ValueError):
        wire.decode_columns_frame(frame + b"XXXXYYYY")
    with pytest.raises(ValueError):  # truncated trace trailer
        wire.decode_columns_frame(
            frame + b"GTRC" + (4).to_bytes(4, "little") + b"\0" * 8
        )


def test_proto_columns_trace_parity_and_ignore():
    cols = _cols(2)
    plain = wire.peer_columns_req_to_pb(cols).SerializeToString()
    assert wire.peer_columns_req_to_pb(cols, trace=[]).SerializeToString() == plain
    traced = wire.peer_columns_req_to_pb(
        cols, trace=[(0, 2, 0xAB, 0xCD)]
    ).SerializeToString()
    assert traced != plain and traced.startswith(plain)
    ic = wire.ingress_from_peer_columns_pb(pc_pb.PeerColumnsReq.FromString(traced))
    assert ic.trace_ctx == [(0, 2, 0xAB, 0xCD)]
    # proto3 unknown-field tolerance — the mechanism that lets a
    # pre-trace peer skip field 8 also skips this crafted field 15:
    unknown = plain + b"\x7a\x04abcd"
    m = pc_pb.PeerColumnsReq.FromString(unknown)
    assert list(m.names) == ["n0", "n1"]


def test_http_frame_trace_negotiation_downgrade(sampled):
    """A columns peer that predates the trailer answers 400 'length
    mismatch'; the sender must resend the SAME frame without the
    trailer (no classic downgrade, no double-send of applied work)."""
    client = PeerClient(
        PeerInfo(grpc_address="127.0.0.1:1", http_address="127.0.0.1:1"),
        fast_test_behaviors(), transport="http",
    )
    calls = []

    def fake_roundtrip(path, data, timeout_s, content_type):
        calls.append(bytes(data))
        if wire.decode_columns_frame(data).trace_ctx is not None:
            raise PeerError(
                "peer returned HTTP 400: invalid columns frame: "
                "columns frame length mismatch",
                http_status=400,
            )
        n = len(wire.decode_columns_frame(data).names)
        from gubernator_tpu.service import ColumnarResult

        return wire.encode_result_frame(ColumnarResult.empty(n))

    client._http_roundtrip = fake_roundtrip
    rc = client._post_columns_inner(
        _cols(2), 1.0, trace=[(0, 2, 0xAB, 0xCD)]
    )
    assert rc.n == 2
    assert len(calls) == 2  # probe with trailer, resend without
    assert client._trace_frames is False
    assert client._columnar is not False  # still columnar, NOT classic
    # subsequent sends skip the trailer immediately
    rc = client._post_columns_inner(_cols(1), 1.0, trace=[(0, 1, 1, 2)])
    assert rc.n == 1 and len(calls) == 3
    client.shutdown(timeout_s=0.1)


# ----------------------------------------------------------------------
# Satellites: logging join, build info, scrape race
# ----------------------------------------------------------------------
def test_log_records_carry_trace_ids(sampled):
    buf = io.StringIO()
    logger = setup_logging(debug=True, stream=buf)
    try:
        with tracing.ingress_span("http", "/x") as sp:
            category_logger("unit").info("traced line")
        category_logger("unit").info("dark line")
        lines = buf.getvalue().splitlines()
        assert f"trace_id={sp.ctx.trace_hex}" in lines[0]
        assert f"span_id={sp.ctx.span_hex}" in lines[0]
        assert "trace_id=-" in lines[1] and "span_id=-" in lines[1]
    finally:
        logger.handlers.clear()


def test_build_info_gauge_labels():
    class _Store:
        def describe_topology(self):
            return "cpu", "8"

    m = Metrics()
    m.set_build_info(_Store())
    text = m.render().decode()
    assert (
        f'gubernator_build_info{{backend="cpu",mesh="8",version="{__version__}"}} 1.0'
        in text
    )


def test_concurrent_scrape_never_drops_stage_samples():
    """Two racing scrapers vs take_pipeline_stats: every observed stage
    sample must be rendered by EXACTLY one scrape (under the scrape
    lock the drain+clear+set+render sequence is atomic; without it one
    scraper's clear() could erase the other's just-drained sample
    before it rendered)."""

    class _Store:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0

        def observe(self, k):
            with self._lock:
                self._count += k

        def take_pipeline_stats(self):
            with self._lock:
                count, self._count = self._count, 0
            return ({"prepare": (count, 0.0, 0.0)} if count else {}), 0, 0

    store = _Store()
    m = Metrics()

    def parse_count(text: str) -> float:
        for line in text.splitlines():
            if line.startswith(
                'gubernator_dispatch_stage_seconds{stage="prepare",stat="count"}'
            ):
                return float(line.rsplit(" ", 1)[1])
        return 0.0

    total_observed = 0
    harvested = []
    barrier = threading.Barrier(2)

    def scraper():
        barrier.wait()
        with m.scrape_lock:
            m.observe_dispatch(store)
            harvested.append(parse_count(m.render().decode()))

    for round_no in range(50):
        store.observe(7)
        total_observed += 7
        harvested.clear()
        ts = [threading.Thread(target=scraper) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # One scraper drained the 7, the other saw an empty delta —
        # never both zero (a dropped sample), never both 7 (a double).
        assert sorted(harvested) == [0.0, 7.0], (round_no, harvested)


def test_debug_routing_and_profile_gate():
    from gubernator_tpu import gateway

    tracing.reset()
    prev = tracing.sample_rate()
    tracing.set_sample_rate(0.0)
    try:
        # typo'd debug paths must 404, not serve plausible data
        status, _, _ = gateway.handle_request(None, "GET", "/debug/tracesfoo", b"")
        assert status == 404
        status, _, _ = gateway.handle_request(None, "GET", "/debug/traces", b"")
        assert status == 200
        # profiling is gated on tracing being enabled
        status, _, body = gateway.handle_request(None, "POST", "/debug/profile", b"{}")
        assert status == 403, body
        tracing.set_sample_rate(1.0)
        # malformed bodies are the caller's fault: 400, not 500
        status, _, _ = gateway.handle_request(
            None, "POST", "/debug/profile", b"[1, 2]"
        )
        assert status == 400
        status, _, _ = gateway.handle_request(
            None, "POST", "/debug/profile", b'{"durationMs": "zzz"}'
        )
        assert status == 400
    finally:
        tracing.set_sample_rate(prev)


def test_profile_concurrent_run_guard():
    """POST /debug/profile: the response names the run (runId) and its
    artifact path (logDir); a SECOND request while one runs answers 409
    carrying the in-flight run's id + path, so racing operators
    converge on the same artifact instead of just being refused."""
    import json as _json

    from gubernator_tpu import gateway

    prev = tracing.sample_rate()
    tracing.set_sample_rate(1.0)
    try:
        status, _, body = gateway.handle_request(
            None, "POST", "/debug/profile", b'{"durationMs": 1500}'
        )
        assert status == 202, body
        doc = _json.loads(body)
        assert doc["runId"] and doc["logDir"]
        status2, _, body2 = gateway.handle_request(
            None, "POST", "/debug/profile", b'{"durationMs": 10}'
        )
        assert status2 == 409, body2
        doc2 = _json.loads(body2)
        assert doc2["runId"] == doc["runId"]
        assert doc2["logDir"] == doc["logDir"]
        # Let the in-flight run drain so later tests see an idle slot.
        t = gateway._profile_state["thread"]
        if t is not None:
            t.join(timeout=60)
    finally:
        tracing.set_sample_rate(prev)


def test_trace_sample_env_validation():
    from gubernator_tpu.config import setup_daemon_config

    conf = setup_daemon_config(env={"GUBER_TRACE_SAMPLE": "0.25"})
    assert conf.behaviors.trace_sample == 0.25
    for bad in ("5", "-1", "abc"):
        with pytest.raises(ValueError):
            setup_daemon_config(env={"GUBER_TRACE_SAMPLE": bad})


def test_shed_records_flight_event(sampled):
    from gubernator_tpu.service import IngressShedError, _IngressGate

    gate = _IngressGate(cap=4, metrics=None)
    gate.admit(3)
    with pytest.raises(IngressShedError):
        gate.admit(2)
    assert any(e["kind"] == "shed" for e in tracing.events_snapshot())


# ----------------------------------------------------------------------
# Integration: one trace across two daemons over the columnar peer hop
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_pair():
    tracing.reset()
    prev = tracing.sample_rate()
    tracing.set_sample_rate(1.0)
    clock = Clock()
    clock.freeze(T0)
    daemons = []
    for _ in range(2):
        behaviors = fast_test_behaviors()
        behaviors.global_sync_wait_s = 3600.0
        behaviors.multi_region_sync_wait_s = 3600.0
        behaviors.trace_sample = 1.0
        d = Daemon(
            DaemonConfig(
                listen_address="127.0.0.1:0",
                grpc_listen_address="127.0.0.1:0",
                cache_size=4096,
                global_cache_size=256,
                behaviors=behaviors,
                peer_discovery_type="static",
            ),
            clock=clock,
        ).start()
        daemons.append(d)
    peers = [d.peer_info for d in daemons]
    for d in daemons:
        d.set_peers(peers)
    yield daemons, clock
    tracing.set_sample_rate(prev)
    tracing.reset()
    for d in daemons:
        d.close()


def _http_get(address: str, path: str) -> dict:
    host, _, port = address.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=10)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return json.loads(r.read())
    finally:
        conn.close()


def test_one_trace_spans_both_daemons(traced_pair):
    daemons, _clock = traced_pair
    entry = daemons[0]
    # Keys this daemon does NOT own: the whole batch must cross the
    # columnar peer hop to daemons[1].
    keys, i = [], 0
    while len(keys) < 4:
        k = f"trace{i}"
        if not entry.service.get_peer(f"tt_{k}").info.is_owner:
            keys.append(k)
        i += 1
    trace_id = "ab" * 16
    traceparent = f"00-{trace_id}-{'12' * 8}-01"
    body = json.dumps(
        {
            "requests": [
                {"name": "tt", "uniqueKey": k, "hits": "1", "limit": "100",
                 "duration": str(9 * SECOND)}
                for k in keys
            ]
        }
    )
    host, _, port = entry.gateway.address.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request(
            "POST", "/v1/GetRateLimits", body=body,
            headers={"Content-Type": "application/json",
                     "traceparent": traceparent},
        )
        r = conn.getresponse()
        payload = json.loads(r.read())
        # the ingress emits the continued trace back to the caller
        assert trace_id in (r.getheader("traceparent") or "")
    finally:
        conn.close()
    assert len(payload["responses"]) == 4
    assert all(resp.get("status", "UNDER_LIMIT") == "UNDER_LIMIT"
               for resp in payload["responses"])

    # ONE trace id, visible via /debug/traces on BOTH daemons: the
    # entry's ingress + peer RPC spans, the owner's batch window and
    # all five pipeline-stage spans (linked, not nested).
    for d in daemons:
        spans = _http_get(
            d.gateway.address, f"/debug/traces?trace_id={trace_id}"
        )["spans"]
        names = {s["name"] for s in spans}
        assert {
            "ingress.http", "peer.rpc", "batch.window",
            "dispatch.prepare", "dispatch.stage", "dispatch.launch",
            "dispatch.fetch", "dispatch.commit",
        } <= names, names
    # span-link rule: the stage spans LINK the ingress trace
    prep = next(s for s in spans if s["name"] == "dispatch.prepare")
    assert prep["trace_id"] != trace_id
    assert any(l["trace_id"] == trace_id for l in prep["links"])
    # /debug/events answers (empty or not — the endpoint must exist)
    assert "events" in _http_get(daemons[0].gateway.address, "/debug/events")


def test_healthz_version_and_build_info(traced_pair):
    daemons, _ = traced_pair
    hc = _http_get(daemons[0].gateway.address, "/healthz")
    assert hc["version"] == __version__
    host, _, port = daemons[0].gateway.address.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=10)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    assert "gubernator_build_info{" in text
    assert f'version="{__version__}"' in text
    assert "gubernator_request_duration_seconds_bucket" in text


def test_trace_sample_zero_keeps_wire_dark(traced_pair):
    """With sampling forced off, the same forwarded request must emit
    no spans and carry no trace bytes (the wire-parity contract)."""
    daemons, _ = traced_pair
    entry = daemons[0]
    tracing.set_sample_rate(0.0)
    try:
        tracing.reset()
        k, i = None, 0
        while k is None:
            cand = f"dark{i}"
            if not entry.service.get_peer(f"tt_{cand}").info.is_owner:
                k = cand
            i += 1
        body = json.dumps(
            {"requests": [
                {"name": "tt", "uniqueKey": k, "hits": "1", "limit": "100",
                 "duration": str(9 * SECOND)},
                {"name": "tt", "uniqueKey": k + "b", "hits": "1",
                 "limit": "100", "duration": str(9 * SECOND)},
            ]}
        )
        host, _, port = entry.gateway.address.partition(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=30)
        try:
            conn.request("POST", "/v1/GetRateLimits", body=body,
                         headers={"Content-Type": "application/json"})
            r = conn.getresponse()
            r.read()
            assert r.getheader("traceparent") is None
        finally:
            conn.close()
        assert tracing.spans_snapshot() == []
    finally:
        tracing.set_sample_rate(1.0)
