"""The C++ edge's own stamps (host_runtime.cpp): first byte read, body
complete, a worker holds the request, the answer staged, its last byte
accepted by the kernel; and the three phases observed from them,
`edge.recv`, `edge.handoff`, `edge.send`.  Each timing case is one that only
the right stamp passes: the 50 ms a client, the workers or a slow reader add
must land in one phase and leave the other two small."""

from __future__ import annotations

import json
import os
import socket
import struct
import threading
import time
import urllib.request

import numpy as np
import pytest

from gubernator_tpu import gateway, native, saturation, tracing, wire
from gubernator_tpu.cluster import fast_test_behaviors
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.daemon import Daemon
from gubernator_tpu.models import shard
from gubernator_tpu.saturation import phase
from gubernator_tpu.utils.clock import Clock

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native runtime unavailable"
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD_S = 0.050
SMALL_S = 0.020  # what a phase that was not held may read on a busy test host


@pytest.fixture(autouse=True)
def _clean():
    saturation.reset()
    tracing.reset()
    yield
    saturation.reset()
    tracing.reset()


def _frame(n, name="edge"):
    return wire.encode_ingress_frame((
        [name] * n, [f"{i}key" for i in range(n)],
        np.zeros(n, np.int32), np.zeros(n, np.int32),
        np.ones(n, np.int64), np.full(n, 1_000_000, np.int64),
        np.full(n, 3_600_000, np.int64),
    ))


def _post(body, ctype=wire.COLUMNS_CONTENT_TYPE):
    return (
        f"POST /v1/GetRateLimits HTTP/1.1\r\nHost: t\r\nContent-Type: {ctype}\r\n"
        f"Content-Length: {len(body)}\r\n\r\n"
    ).encode() + body


def _read_response(sock, buf=b""):
    """(head, body, what was read past the body: a pipelined answer's start)."""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("server closed mid-response")
        buf += chunk
    head, _, rest = buf.partition(b"\r\n\r\n")
    (clen,) = [int(ln.split(b":")[1]) for ln in head.split(b"\r\n")
               if ln.lower().startswith(b"content-length")]
    while len(rest) < clen:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("server closed mid-body")
        rest += chunk
    return head, rest[:clen], rest[clen:]


@pytest.fixture
def bare():
    """An HttpEdge and an IngressBatcher that owns every key, with this test
    as the only worker and the only pump."""
    edge = native.HttpEdge("127.0.0.1:0")
    b = native.IngressBatcher()
    b.set_ring(np.zeros(0, np.uint64), np.zeros(0, np.uint8), all_self=True,
               enabled=True, cap_lanes=0, max_frame_lanes=16384, behavior_mask=1 | 2 | 4 | 16)
    socks = []

    def connect(rcvbuf=None):
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        if rcvbuf:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        s.connect(("127.0.0.1", edge.port))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.settimeout(30.0)
        socks.append(s)
        return s

    yield edge, b, connect
    for s in socks:
        s.close()
    b.stop()
    edge.shutdown()
    edge.free()
    b.free()


def _answer(b, tb):
    n = tb.n
    b.complete(tb, np.zeros(n, np.int32), np.full(n, 1_000_000, np.int64),
               np.full(n, 999_999, np.int64), np.zeros(n, np.int64))


def _wait_for(pred, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert pred()


def _sends(edge, want, timeout_s=5.0):
    got = []
    deadline = time.monotonic() + timeout_s
    while len(got) < want and time.monotonic() < deadline:
        got += edge.drain_sends()
        time.sleep(0.002)
    assert len(got) == want, got
    return got


def test_the_edges_clock_is_pythons_monotonic_clock():
    lib = native._get_lib()
    worst = 0
    for _ in range(20):
        before = time.monotonic_ns()
        inside = lib.gt_mono_ns()
        after = time.monotonic_ns()
        assert before <= inside <= after
        worst = max(worst, after - before)
    assert worst < 1_000_000  # the two agree within 1 ms (within the bracket, in fact)


def test_a_body_sent_in_two_halves_is_recv_and_not_handoff(bare):
    edge, b, connect = bare
    s = connect()
    raw = _post(_frame(64))
    # This test's own readings, on the stamps' clock (the test above).
    t_before_first_half = time.monotonic_ns()
    s.sendall(raw[: len(raw) // 2])
    t_after_first_half = time.monotonic_ns()
    time.sleep(HELD_S)
    t_before_second_half = time.monotonic_ns()
    s.sendall(raw[len(raw) // 2:])
    assert edge.next(timeout_ms=2000, ingress=b) is native.FAST_LANE
    tb = b.take(65536, timeout_ms=2000)
    ((token, t_first_byte, t_body, t_arrival),) = tb.frame_stamps.tolist()
    assert token > 0
    assert t_first_byte >= t_before_first_half
    # The body cannot be whole before its second half was sent.
    assert t_body >= t_before_second_half
    # `t_first_byte` is read after the acceptor's `read` returns: what the
    # acceptor took to wake comes off the hold, as off the twin's below.
    assert t_body - t_first_byte >= 0.9 * HELD_S * 1e9, (
        "the acceptor stamped the first byte "
        f"{t_first_byte - t_after_first_half} ns after it was sent"
    )
    assert 0 <= t_arrival - t_body < SMALL_S * 1e9
    assert t_arrival + int(tb.frame_age_us[0]) * 1000 <= time.monotonic_ns()  # the take's own reading
    _answer(b, tb)
    _read_response(s)


def test_held_workers_are_handoff_and_not_recv(bare):
    edge, b, connect = bare
    s = connect()
    s.sendall(_post(_frame(64)))
    time.sleep(HELD_S)  # every worker (this thread) is busy elsewhere
    assert edge.next(timeout_ms=2000, ingress=b) is native.FAST_LANE
    tb = b.take(65536, timeout_ms=2000)
    ((_, t_first_byte, t_body, t_arrival),) = tb.frame_stamps.tolist()
    assert t_arrival - t_body >= 0.9 * HELD_S * 1e9
    assert 0 <= t_body - t_first_byte < SMALL_S * 1e9
    _answer(b, tb)
    _read_response(s)


def test_a_client_that_reads_late_is_send(bare):
    """The answer is 8 MB, not a 4096-lane frame's 114,706 bytes: the kernel's
    send buffer takes those whole on loopback however small the reader's
    window, and `t_last_byte` is the kernel's acceptance, not the client's
    read.  8 MB it cannot take before the client reads."""
    edge, _, connect = bare
    s = connect(rcvbuf=4096)
    s.sendall(_post(b'{"requests": []}', "application/json"))
    token, _, _, _, (_, t_body) = edge.next(timeout_ms=2000)
    edge.respond(token, 200, b"x" * (8 << 20))
    time.sleep(HELD_S)
    _, body, _ = _read_response(s)
    assert len(body) == 8 << 20
    ((sent_token, t_staged, t_last_byte),) = _sends(edge, 1)
    assert sent_token == token
    assert t_body <= t_staged
    assert t_last_byte - t_staged >= 0.9 * HELD_S * 1e9
    st = edge.stats()
    assert st["sends"] > 1 and st["epolloutRounds"] >= st["sends"] and st["sendBytes"] > 8 << 20


def test_pipelined_requests_each_get_stamps_of_their_own_in_order(bare):
    edge, b, connect = bare
    s = connect()
    one = _post(_frame(4096))
    assert len(one) > 3 * 65536  # several reads a request: the second's first byte has its own
    s.sendall(one + one)
    rows = []
    while len(rows) < 2:
        assert edge.next(timeout_ms=2000, ingress=b) is native.FAST_LANE
        tb = b.take(4096, timeout_ms=2000)  # a frame a take
        rows += tb.frame_stamps.tolist()
        _answer(b, tb)
    rest = b""
    for _ in range(2):
        _, _, rest = _read_response(s, rest)
    sends = {tok: (staged, last) for tok, staged, last in _sends(edge, 2)}
    (tok1, fb1, body1, arr1), (tok2, fb2, body2, arr2) = rows
    assert tok2 > tok1 and fb2 > fb1 and body2 >= body1
    for tok, fb, body, arr in rows:
        staged, last = sends[tok]
        assert fb <= body <= arr <= staged <= last
    assert sends[tok1][1] <= sends[tok2][1]  # answers leave in the order asked


def test_the_json_paths_request_carries_its_stamps(bare):
    edge, _, connect = bare
    s = connect()
    s.sendall(_post(b'{"requests": []}', "application/json"))
    got = edge.next(timeout_ms=2000)
    token, method, path, body, (t_first_byte, t_body) = got
    assert (method, path, body) == ("POST", "/v1/GetRateLimits", b'{"requests": []}')
    assert 0 < t_first_byte <= t_body <= time.monotonic_ns()
    edge.respond(token, 200, b"{}")
    _read_response(s)
    ((sent_token, t_staged, t_last_byte),) = _sends(edge, 1)
    assert sent_token == token and t_body <= t_staged <= t_last_byte


def test_the_send_ring_is_bounded_and_a_dead_connection_leaks_no_record(bare):
    edge, _, connect = bare
    s = connect()
    ask = b"GET /x HTTP/1.1\r\nHost: t\r\nContent-Length: 0\r\n\r\n"
    for _ in range(4096 + 10):  # nobody drains
        s.sendall(ask)
        got = edge.next(timeout_ms=2000)
        edge.respond(got[0], 200, b"{}")
        _read_response(s)
    _wait_for(lambda: edge.stats()["sendRingDropped"] == 10)
    st = edge.stats()
    assert sorted(st) == sorted(native.HttpEdge.STAT_KEYS)
    assert st["requests"] == 4106 and st["reads"] >= 4106 and st["sends"] >= 4106
    assert st["readBytes"] == 4106 * len(ask)
    kept = edge.drain_sends()  # one call takes all there is, oldest first
    assert len(kept) == 4096 and edge.drain_sends() == []
    tokens = [tok for tok, _, _ in kept]
    assert tokens == sorted(tokens) and tokens[-1] == got[0] and tokens[0] == got[0] - 4095
    # A connection that dies with its answer half written: the mark goes with it.
    dying = connect(rcvbuf=4096)
    dying.sendall(ask)
    dead_token = edge.next(timeout_ms=2000)[0]
    sent_before = edge.stats()["sendBytes"]
    edge.respond(dead_token, 200, b"x" * (8 << 20))  # far more than the socket takes
    _wait_for(lambda: edge.stats()["sendBytes"] > sent_before)  # the answer is under way
    assert edge.stats()["sendBytes"] - sent_before < 8 << 20
    conns = lambda: sum(a["conns"] for a in edge.acceptor_stats())  # noqa: E731
    assert conns() == 2
    dying.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0))
    dying.close()  # a reset
    _wait_for(lambda: conns() == 1)
    # The live connection's next answer is the only record there is.
    s.sendall(ask)
    live_token = edge.next(timeout_ms=2000)[0]
    edge.respond(live_token, 200, b"{}")
    _read_response(s)
    assert [tok for tok, _, _ in _sends(edge, 1)] == [live_token]
    time.sleep(0.05)
    assert edge.drain_sends() == []
    st = edge.stats()
    assert st["requests"] == 4108 and st["sendRingDropped"] == 10


def test_several_observations_of_a_phase_under_one_lock_hold():
    saturation.observe_phases("edge.send", [0.001, 0.003, 0.002])
    saturation.observe_phases("edge.send", [])
    saturation.observe_phase("edge.send", 0.004)
    snap = saturation.phase_snapshot()["edge.send"]
    assert (snap["count"], snap["sum_ms"], snap["max_ms"], snap["n_samples"]) == (4, 10.0, 4.0, 4)


# ---------------------------------------------------------------------
# Through a daemon: the phases, on both paths
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def daemon():
    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0
    behaviors.multi_region_sync_wait_s = 3600.0
    behaviors.native_ingress = True
    d = Daemon(
        DaemonConfig(
            listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0",
            cache_size=32768, global_cache_size=256, behaviors=behaviors,
            peer_discovery_type="static", native_http=True, warmup_shapes=[],
        ),
        clock=Clock(),
    ).start()
    d.set_peers([d.peer_info])
    yield d
    d.close()


def _counts():
    snap = saturation.phase_snapshot()
    return {p: snap.get(p, {}).get("count", 0) for p in ("edge.recv", "edge.handoff", "edge.send")}


@pytest.mark.parametrize("path", ["native-lane", "native-lane-call", "json"])
def test_both_paths_observe_all_three_phases(daemon, path):
    """A frame and (since PR 47) a plain classic call ride the native lane; a
    call the lane hands back (a float) takes the JSON path, worker and all."""
    if path == "native-lane":
        data, ctype = _frame(16, name="both"), wire.COLUMNS_CONTENT_TYPE
    else:
        data = json.dumps({"requests": [{"name": "both", "uniqueKey": "j",
                                         "hits": 1.0 if path == "json" else 1,
                                         "limit": 10, "duration": 60000}]}).encode()
        ctype = "application/json"
    before = daemon.gateway.pump.stats()
    req = urllib.request.Request(
        f"http://{daemon.gateway.address}/v1/GetRateLimits", data=data,
        headers={"Content-Type": ctype})
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.status == 200
    after = daemon.gateway.pump.stats()
    grown = {k: after[k] - before[k] for k in ("frames", "calls", "callFallbacks", "fallbacks")}
    assert grown == {"frames": path == "native-lane", "calls": path == "native-lane-call",
                     "callFallbacks": path == "json", "fallbacks": 0}
    # The stamps are observed after the answer has left (`pump.account`; on the
    # JSON path when the worker has gathered EDGE_FLUSH requests or idles), the
    # answer's own record by the next of those or the pump's idle tick.
    _wait_for(lambda: all(v >= 1 for v in _counts().values()))
    snap = saturation.phase_snapshot()
    for name in ("edge.recv", "edge.handoff", "edge.send"):
        assert 0.0 <= snap[name]["max_ms"] < 1000.0, (name, snap[name])
    edge_doc = daemon.service.debug_status()["edge"]
    assert edge_doc["requests"] >= 1 and edge_doc["reads"] >= 1 and edge_doc["sends"] >= 1


def test_a_burst_on_the_json_path_is_observed_once_a_request_not_drained_once_a_request(daemon, monkeypatch):
    """A worker gathers the JSON path's stamps and observes them together
    (EDGE_FLUSH, or when it idles): every request is counted once in each
    phase, and the send ring is drained far fewer times than requests came."""
    drains = []
    drain = native.HttpEdge.drain_sends
    monkeypatch.setattr(native.HttpEdge, "drain_sends", lambda self: drains.append(1) or drain(self))
    n = 3 * gateway.NativeGatewayServer.EDGE_FLUSH
    # A float: the native lane hands such a call to the JSON path whole (a
    # plain call rides the lane since PR 47 and is observed once a take).
    data = json.dumps({"requests": [{"name": "burst", "uniqueKey": "b", "hits": 0.0,
                                     "limit": 10, "duration": 60000}]}).encode()
    raw = _post(data, "application/json")
    before = _counts()
    handed_back = daemon.gateway.pump.stats()["callFallbacks"]
    s = socket.create_connection(("127.0.0.1", int(daemon.gateway.address.rsplit(":", 1)[1])))
    s.settimeout(30.0)
    try:
        drains.clear()
        t0 = time.monotonic()
        for _ in range(n):
            s.sendall(raw)
            _read_response(s)
        in_burst, burst_s = len(drains), time.monotonic() - t0
    finally:
        s.close()
    _wait_for(lambda: all(_counts()[p] == before[p] + n for p in before))
    assert daemon.gateway.pump.stats()["callFallbacks"] - handed_back == n
    # A flush a worker per EDGE_FLUSH requests, and one per 200 ms that a
    # worker (four) or the pump idles: not one a request.
    assert in_burst <= n // gateway.NativeGatewayServer.EDGE_FLUSH + 5 + 25 * burst_s, (in_burst, burst_s)


def test_the_in_request_phases_add_up_to_the_residence(daemon, monkeypatch):
    """One frame in flight: a request's residence in the daemon
    (t_last_byte - t_first_byte) is the sum of the depth-0 phases the
    benchmark's reader subtracts from the client's round trip, less the native
    parse, which lies inside batch.window and so is counted twice."""
    first_byte, last_byte = {}, {}
    take, drain = native.IngressBatcher.take, native.HttpEdge.drain_sends

    def taking(self, *a, **kw):
        tb = take(self, *a, **kw)
        if tb is not None:
            first_byte.update({row[0]: row[1] for row in tb.frame_stamps.tolist()})
        return tb

    def draining(self):
        recs = drain(self)
        last_byte.update({tok: last for tok, _, last in recs})
        return recs

    monkeypatch.setattr(native.IngressBatcher, "take", taking)
    monkeypatch.setattr(native.HttpEdge, "drain_sends", draining)
    # One wait of a native take lies in no phase: ColumnsHandle.result() fetches
    # before the ordered drain enters `dispatch.fetch` (so that waiters overlap
    # their read-backs), and that first fetch is the one that waits for the
    # device.  It is timed here and added, so that the sum still holds every
    # other part to the residence (PERF.md §7).
    unphased_s = [0.0]
    in_drain = threading.local()
    fetch, resolve = shard.ColumnsHandle._fetch, shard.ColumnsHandle._do_resolve

    def fetching(self):
        t = time.perf_counter()
        try:
            return fetch(self)
        finally:
            if not getattr(in_drain, "on", False):
                unphased_s[0] += time.perf_counter() - t

    def resolving(self):
        in_drain.on = True
        try:
            resolve(self)
        finally:
            in_drain.on = False

    monkeypatch.setattr(shard.ColumnsHandle, "_fetch", fetching)
    monkeypatch.setattr(shard.ColumnsHandle, "_do_resolve", resolving)
    # Nor do a take's observers (tap, folds, sketch: `_observe`, booked under
    # the off-request `pump.account`): they run once the take has launched, in
    # front of that fetch, on the done pool's thread.  On a chip the device
    # computes meanwhile and the fetch's wait is the shorter by them; on this
    # CPU the program may have ended first, so they are timed and added too.
    observers_s = [0.0]
    observe = gateway.NativeIngressPump._observe

    def observing(self, tb, bt):
        t = time.perf_counter()
        try:
            return observe(self, tb, bt)
        finally:
            observers_s[0] += time.perf_counter() - t

    monkeypatch.setattr(gateway.NativeIngressPump, "_observe", observing)
    with open(os.path.join(REPO, "chipbench", "layer_metrics", "edge.unattributed_ms_per_req.json")) as f:
        off_request = set(json.load(f)["params"]["off_request"])
    in_request = [p for p, depth in saturation.WATERFALL if depth == 0 and p not in off_request]
    assert {"edge.recv", "edge.handoff", "edge.send"} <= set(in_request)
    raw = _post(_frame(4096, name="sum"))
    s = socket.create_connection(("127.0.0.1", int(daemon.gateway.address.rsplit(":", 1)[1])))
    s.settimeout(30.0)
    try:
        for _ in range(5):  # warm: the first take compiles its bucket
            s.sendall(raw)
            _read_response(s)
        _wait_for(lambda: len(last_byte) >= 5)
        saturation.reset()
        first_byte.clear()
        last_byte.clear()
        for _ in range(200):
            s.sendall(raw)
            _read_response(s)
        _wait_for(lambda: len(last_byte) >= 200)
    finally:
        s.close()
    assert sorted(first_byte) == sorted(last_byte) and len(last_byte) == 200
    residence_ms = sum(last_byte[t] - first_byte[t] for t in last_byte) / 1e6
    snap = saturation.phase_snapshot()
    assert snap["edge.recv"]["count"] == snap["edge.handoff"]["count"] == snap["edge.send"]["count"] == 200
    named_ms = (sum(snap.get(p, {}).get("sum_ms", 0.0) for p in in_request)
                - snap["ingress.parse"]["sum_ms"] + (unphased_s[0] + observers_s[0]) * 1e3)
    # What is left is the interpreter between one phase's end and the next's
    # start on the pump's and the done pool's threads (the take's views built,
    # the dispatch entered, the future queued): 5 to 9% of a frame here.  A
    # stamp or a phase that overlapped another would push the sum past the
    # residence (2% of room: the phases are timed on perf_counter, the
    # residence on the stamps' clock); one that left a hole would drop it
    # under the bound.
    assert 0.85 * residence_ms <= named_ms <= 1.02 * residence_ms, json.dumps(
        {"residence_ms": residence_ms, "unphased fetch": unphased_s[0] * 1e3,
         "observers": observers_s[0] * 1e3,
         **{p: snap.get(p, {}).get("sum_ms") for p in in_request}})


def test_outside_a_profiler_session_a_take_builds_no_trace_metadata(daemon, monkeypatch):
    noted, formatted = [], []
    note = phase.note
    monkeypatch.setattr(phase, "note", lambda self, **ids: (noted.append((self.name, ids)), note(self, **ids)))
    monkeypatch.setattr(saturation, "edge_trace_note",
                        lambda *a: formatted.append(a) or {})
    assert not saturation._profiler_session_on()
    before = _counts()
    req = urllib.request.Request(
        f"http://{daemon.gateway.address}/v1/GetRateLimits", data=_frame(16, name="quiet"),
        headers={"Content-Type": wire.COLUMNS_CONTENT_TYPE})
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.status == 200
    _wait_for(lambda: _counts()["edge.send"] > before["edge.send"])
    assert _counts()["edge.recv"] == before["edge.recv"] + 1  # the stamps were read
    assert formatted == []
    assert [ids for name, ids in noted if name in ("pump.admit", "pump.account")] == []
    # models/shard.py's note of the ticket is as before.
    prepares = [ids for name, ids in noted if name == "dispatch.prepare"]
    assert prepares and all("ticket" in ids for ids in prepares)


def test_a_sampled_takes_spans_carry_its_frames_tokens(monkeypatch):
    """The three edge spans of a request share its token with the take's
    pump.admit span, whose trace holds the dispatch spans and their ticket."""
    bt = tracing.BatchTrace(())
    stamps = [[7, 100, 200, 300], [8, 150, 250, 350]]
    with phase("pump.admit", bt, frames=2, lanes=8) as ph:
        gateway.NativeIngressPump._trace_edge(ph, bt, 0, 400, stamps)

    class _Edge:
        def drain_sends(self):
            return [[6, 50, 90]]

    class _Pump:
        service = type("S", (), {"native_edges": [_Edge()]})()

    with phase("pump.account", bt) as ph:
        gateway.NativeIngressPump._edge_sends(_Pump(), ph, bt)
    spans = {(s["name"], s["attrs"].get("token")): s for s in tracing.spans_snapshot(bt.ctx.trace_hex)
             if s["name"].startswith("edge.")}
    assert set(spans) == {("edge.recv", 7), ("edge.handoff", 7), ("edge.recv", 8),
                          ("edge.handoff", 8), ("edge.send", 6)}
    assert (spans["edge.recv", 7]["start_ns"], spans["edge.recv", 7]["dur_ns"]) == (100, 100)
    assert (spans["edge.send", 6]["start_ns"], spans["edge.send", 6]["dur_ns"]) == (50, 40)
    (admit,) = [s for s in tracing.spans_snapshot(bt.ctx.trace_hex) if s["name"] == "pump.admit"]
    assert admit["attrs"]["tokens"] == "7;8"
    assert saturation.phase_snapshot()["edge.send"]["count"] == 1


def test_the_trace_note_of_a_take_and_of_drained_answers():
    assert saturation.edge_trace_note(1000, 1400, [[7, 1100, 1200, 1300]]) == {
        "mono_ns": 1000, "take": 400, "edge": "7:100:200:300"}
    assert saturation.edge_trace_note(1000, sends=[[6, 950, 990], [7, 1500, 1600]]) == {
        "mono_ns": 1000, "sends": "6:-50:-10;7:500:600"}
