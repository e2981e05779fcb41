"""A classic JSON `POST /v1/GetRateLimits` call on the native ingress lane
(host_runtime.cpp `gt_ingress_submit` / `gt_ingress_complete`): parsed, queued,
coalesced and answered in C++ beside the GUBC frames, Python running once a take.

Two daemons on ONE device share a frozen clock: `lane` serves with the native
ingress pump, `python` is the same daemon with `native_ingress` off, whose every
call takes `handle_request_async`: the route every call took before, and the
lane's fallback.  Held here:

(a) byte identity: for a corpus of bodies the status line, headers and body the
    lane sends equal the Python route's for the same body against a store in the
    same state, `tests/oracle.py` agrees with both, and the lane kept the call;
(b) the fallback matrix: what the lane cannot answer exactly as Python would is
    handed to Python whole (its historical bytes), counted `callFallbacks`, and
    queues nothing; the shed bound answers its 429 natively, in Python's words;
(c) a mixed take: a 64-lane GUBC frame and three calls queued behind a stalled
    pump come out of ONE take, each answered in its own encoding, its own slice;
(d) NO_BATCHING calls ride the express queue;
(e) the accounting: requests counted and timed once a call, the columnar-frame
    counter not at all, the black box holds the take's frame and none of its
    calls, the audit stays clean."""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from gubernator_tpu import native, wire
from gubernator_tpu.config import MAX_BATCH_SIZE
from gubernator_tpu.gateway import NativeIngressPump
from gubernator_tpu.service import IngressShedError
from gubernator_tpu.types import Algorithm, Behavior, PeerInfo, RateLimitRequest

from . import oracle as orc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # chipbench, the benchmark beside the program

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the native ingress lane needs the host runtime")

T0 = 1_790_000_000_000  # 2026-09-21T13:46:40Z: a day and a month have hours left
RPC = "/pb.gubernator.V1/GetRateLimits"
NO_BATCHING, GLOBAL, CALENDAR, RESET = (
    int(Behavior.NO_BATCHING), int(Behavior.GLOBAL),
    int(Behavior.DURATION_IS_GREGORIAN), int(Behavior.RESET_REMAINING))
BEHAVIOR_NAMES = {0: "BATCHING", NO_BATCHING: "NO_BATCHING", GLOBAL: "GLOBAL",
                  CALENDAR: "DURATION_IS_GREGORIAN", RESET: "RESET_REMAINING"}


def _daemon(clock, *, native_ingress: bool):
    from gubernator_tpu.cluster import fast_test_behaviors
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import Daemon

    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0
    behaviors.multi_region_sync_wait_s = 3600.0
    behaviors.native_ingress = native_ingress
    daemon = Daemon(DaemonConfig(
        listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0", cache_size=8192,
        global_cache_size=256, behaviors=behaviors, peer_discovery_type="static",
        native_http=True, devices=jax.devices()[:1], warmup_shapes=[]), clock=clock).start()
    daemon.set_peers([daemon.peer_info])
    return daemon


@pytest.fixture(scope="module")
def pair():
    from gubernator_tpu.utils.clock import Clock

    clock = Clock()
    clock.freeze(T0)
    lane = _daemon(clock, native_ingress=True)
    python = _daemon(clock, native_ingress=False)
    try:
        yield lane, python
    finally:
        lane.close()
        python.close()


def _port(daemon) -> int:
    return daemon.gateway._edge.port


def _get(daemon, path: str) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{_port(daemon)}{path}", timeout=30) as r:
        return json.loads(r.read())


def _head(body: bytes, ctype: str = "application/json") -> bytes:
    return (f"POST /v1/GetRateLimits HTTP/1.1\r\nHost: t\r\nContent-Type: {ctype}\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


def _read_answer(sock) -> bytes:
    """One whole HTTP answer off an open socket, as sent."""
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        assert chunk, "the daemon closed the connection"
        buf += chunk
    head, _, rest = buf.partition(b"\r\n\r\n")
    length = next(int(line.split(b":")[1]) for line in head.split(b"\r\n")
                  if line.lower().startswith(b"content-length"))
    while len(rest) < length:
        rest += sock.recv(65536)
    return head + b"\r\n\r\n" + rest[:length]


def _post(port: int, body: bytes, ctype: str = "application/json") -> bytes:
    s = socket.create_connection(("127.0.0.1", port))
    s.settimeout(60.0)
    try:
        s.sendall(_head(body, ctype))
        return _read_answer(s)
    finally:
        s.close()


def _check(name, key, hits=1, limit=10, duration=60_000, algorithm=0, behavior=0):
    return RateLimitRequest(name=name, unique_key=key, hits=hits, limit=limit,
                            duration=duration, algorithm=Algorithm(algorithm), behavior=behavior)


def _body(checks, enums: str = "names", key_field: str = "uniqueKey", lead: bytes = b"") -> bytes:
    """The call a client would send for `checks`; `enums` says how it writes
    the two enums: by name, as a number, or as a number in quotes."""
    def enum(value: int, names) -> object:
        if enums == "names":
            return names[value]
        return value if enums == "ints" else str(value)

    return lead + json.dumps({"requests": [
        {"name": r.name, key_field: r.unique_key, "hits": str(r.hits), "limit": str(r.limit),
         "duration": str(r.duration),
         "algorithm": enum(int(r.algorithm), ("TOKEN_BUCKET", "LEAKY_BUCKET")),
         "behavior": enum(int(r.behavior), BEHAVIOR_NAMES)}
        for r in checks]}, separators=(",", ":")).encode()


def _frame_of(checks) -> bytes:
    """The kind-5 frame a columnar client would send for `checks`."""
    return wire.encode_ingress_frame((
        [r.name for r in checks], [r.unique_key for r in checks],
        np.array([int(r.algorithm) for r in checks], np.int32),
        np.array([r.behavior for r in checks], np.int32),
        np.array([r.hits for r in checks], np.int64), np.array([r.limit for r in checks], np.int64),
        np.array([r.duration for r in checks], np.int64)))


def _oracle_answer(cache, checks, now: int = T0) -> bytes:
    """The whole HTTP answer the oracle's rows make, in the gateway's JSON."""
    rows = [orc.apply(cache, r, now) for r in checks]
    body = json.dumps({"responses": [
        {"status": "OVER_LIMIT" if int(r.status) else "UNDER_LIMIT", "limit": str(r.limit),
         "remaining": str(r.remaining), "resetTime": str(r.reset_time)} for r in rows
    ]}, separators=(",", ":")).encode()
    return (f"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


def _corpus() -> dict:
    """case -> the calls of the case, in order: (checks, how the body is written)."""
    thousand = [_check("big", f"{i}k", hits=1 + i % 3, limit=100, algorithm=i % 2)
                for i in range(MAX_BATCH_SIZE)]
    return {
        "two_plain_checks": [([_check("a", "p1"), _check("a", "p2", hits=3)], {})],
        "one_check": [([_check("a", "solo", hits=2)], {})],
        "same_key_twice": [([_check("a", "twice", hits=4), _check("a", "twice", hits=5)], {})],
        "token_and_leaky": [([_check("a", "tok", hits=2), _check("a", "leak", hits=2, algorithm=1)], {})] * 2,
        "over_the_limit": [([_check("a", "over", hits=6), _check("a", "over2", hits=1, limit=1)], {})] * 3,
        "reset_remaining": [
            ([_check("a", "rst", hits=7), _check("a", "rst2", hits=1)], {}),
            ([_check("a", "rst", hits=1, behavior=RESET), _check("a", "rst2", hits=1)], {}),
        ],
        "gregorian_daily_and_monthly": [([
            _check("cal", "day", hits=2, duration=2, behavior=CALENDAR, algorithm=1),
            _check("cal", "month", hits=3, duration=4, behavior=CALENDAR),
        ], {})] * 2,
        "no_batching": [([_check("a", "nb1", behavior=NO_BATCHING), _check("a", "nb2")], {})],
        "global_in_a_one_node_ring": [([_check("a", "g1", behavior=GLOBAL), _check("a", "g2")], {})],
        "unique_key_snake_case": [([_check("a", "snake1"), _check("a", "snake2")], {"key_field": "unique_key"})],
        "enums_as_ints": [([_check("a", "ei", algorithm=1), _check("a", "ei2", behavior=RESET)], {"enums": "ints"})],
        "enums_as_quoted_ints": [([_check("a", "eq", algorithm=1), _check("a", "eq2")], {"enums": "quoted"})],
        "leading_whitespace": [([_check("a", "ws1"), _check("a", "ws2")], {"lead": b" \r\n\t  \n  "})],
        "a_thousand_checks": [(thousand, {})],
    }


CORPUS = _corpus()


@pytest.mark.parametrize("case", list(CORPUS))
def test_the_lane_answers_a_call_byte_for_byte_as_the_python_route(pair, case):
    lane, python = pair
    cache = orc.OracleCache()
    for checks, how in CORPUS[case]:
        body = _body(checks, **how)
        before = lane.gateway.pump.stats()
        got = _post(_port(lane), body)
        want = _post(_port(python), body)
        assert got == want, (case, got[:300], want[:300])
        assert got == _oracle_answer(cache, checks), (case, got[:300])
        after = lane.gateway.pump.stats()
        assert after["calls"] - before["calls"] == 1, case  # the lane kept it
        assert after["callFallbacks"] == before["callFallbacks"], case
        assert after["lanes"] - before["lanes"] == len(checks)
        assert after["frames"] == before["frames"] and after["fallbacks"] == before["fallbacks"]
    assert python.gateway.pump is None  # the reference really is the Python route
    assert _get(lane, "/debug/audit")["violationTotal"] == 0


# ---------------------------------------------------------------------
# (b) the fallback matrix
# ---------------------------------------------------------------------

def _plain(key: str) -> str:
    return ('{"name":"fb","uniqueKey":"%s","hits":"1","limit":"10","duration":"60000"}' % key)


FALLBACK_BODIES = {
    "an_escape_in_a_key": b'{"requests":[{"name":"fb","uniqueKey":"e\\u0041\\n","hits":"1","limit":"10","duration":"60000"},'
                          + _plain("esc2").encode() + b"]}",
    "a_float": b'{"requests":[{"name":"fb","uniqueKey":"fl","hits":1.0,"limit":"10","duration":"60000"},'
               + _plain("fl2").encode() + b"]}",
    "a_behaviour_list": b'{"requests":[{"name":"fb","uniqueKey":"bl","hits":"1","limit":"10","duration":"60000",'
                        b'"behavior":["RESET_REMAINING"]},' + _plain("bl2").encode() + b"]}",
    "an_empty_unique_key": ('{"requests":[%s,%s]}' % (_plain(""), _plain("eu2"))).encode(),
    "an_empty_name": b'{"requests":[{"name":"","uniqueKey":"en","hits":"1","limit":"10","duration":"60000"},'
                     + _plain("en2").encode() + b"]}",
    "a_bad_algorithm": b'{"requests":[{"name":"fb","uniqueKey":"ba","hits":"1","limit":"10","duration":"60000",'
                       b'"algorithm":7},' + _plain("ba2").encode() + b"]}",
    "a_bad_behaviour_name": b'{"requests":[{"name":"fb","uniqueKey":"bb","hits":"1","limit":"10","duration":"60000",'
                            b'"behavior":"SOMETIMES"},' + _plain("bb2").encode() + b"]}",
    "nested_values": b'{"requests":[{"name":"fb","uniqueKey":{"k":"nv"},"hits":"1","limit":"10","duration":"60000"}]}',
    "duplicate_requests": ('{"requests":[%s],"requests":[%s,%s]}' % (_plain("d0"), _plain("d1"), _plain("d2"))).encode(),
    "trailing_bytes": ('{"requests":[%s,%s]} x' % (_plain("tb1"), _plain("tb2"))).encode(),
    "no_checks": b'{"requests":[]}',
    "a_thousand_and_one_checks": ('{"requests":[%s]}' % ",".join(
        _plain(f"{i}big") for i in range(MAX_BATCH_SIZE + 1))).encode(),
    "invalid_utf8": b'{"requests":[{"name":"fb","uniqueKey":"u\xff\xfe","hits":"1","limit":"10","duration":"60000"},'
                    + _plain("u2").encode() + b"]}",
    "utf8_torn_between_two_keys": b'{"requests":[{"name":"fb","uniqueKey":"t\xc3","hits":"1","limit":"10","duration":"60000"},'
                                  b'{"name":"\xa9","uniqueKey":"t2","hits":"1","limit":"10","duration":"60000"}]}',
    "a_week_of_calendar": b'{"requests":[{"name":"fb","uniqueKey":"wk","hits":"1","limit":"10","duration":"3",'
                          b'"behavior":"DURATION_IS_GREGORIAN"},' + _plain("wk2").encode() + b"]}",
}


def _lane_counts(daemon) -> dict:
    stats = daemon.gateway.pump.stats()
    return {k: stats[k] for k in ("calls", "callFallbacks", "frames", "fallbacks", "lanes",
                                  "batches", "pendingFrames", "pendingLanes")}


def _handed_back(before: dict, after: dict) -> None:
    """One call was offered to the lane and handed back; nothing was queued."""
    assert after["callFallbacks"] - before["callFallbacks"] == 1, (before, after)
    for kept in ("calls", "frames", "fallbacks", "lanes", "batches"):
        assert after[kept] == before[kept], (kept, before, after)
    assert after["pendingFrames"] == 0 and after["pendingLanes"] == 0


@pytest.mark.parametrize("case", list(FALLBACK_BODIES))
def test_what_python_alone_answers_exactly_is_handed_to_python_whole(pair, case):
    lane, python = pair
    body = FALLBACK_BODIES[case]
    before = _lane_counts(lane)
    got = _post(_port(lane), body)
    want = _post(_port(python), body)
    assert got == want, (case, got[:300], want[:300])
    _handed_back(before, _lane_counts(lane))
    # Python's own words: an error object for the lane, or for the call.
    status = int(got.split(b" ", 2)[1])
    answer = json.loads(got.partition(b"\r\n\r\n")[2])
    if case in ("an_empty_unique_key", "an_empty_name", "a_week_of_calendar"):
        assert status == 200 and answer["responses"][0]["error"] and "error" not in answer["responses"][1]
    elif case == "a_thousand_and_one_checks":
        assert status == 400 and answer["code"] == 11 and "too large" in answer["message"]
    elif case == "no_checks":
        assert status == 200 and answer == {"responses": []}
    elif case in ("trailing_bytes", "utf8_torn_between_two_keys"):
        assert status == 400 and answer["code"] == 3
    elif case in ("a_bad_algorithm", "a_bad_behaviour_name"):
        assert status == 500 and answer["code"] == 13
    else:  # JSON the native parser refuses and json.loads reads, or bytes Python never decodes
        assert status == 200 and all("error" not in r for r in answer["responses"])


def test_a_disabled_lane_hands_every_call_to_python(pair):
    lane, python = pair
    pump = lane.gateway.pump
    body = _body([_check("dis", "d1"), _check("dis", "d2")])
    pump.batcher.disable()
    try:
        before = _lane_counts(lane)
        got = _post(_port(lane), body)
        _handed_back(before, _lane_counts(lane))
    finally:
        pump.update_ring()
    assert got == _post(_port(python), body)
    before = _lane_counts(lane)
    again = _post(_port(lane), body)  # enabled again: the lane keeps the same call
    assert _lane_counts(lane)["calls"] - before["calls"] == 1
    assert again == _post(_port(python), body)


@pytest.fixture(scope="module")
def two_nodes():
    """The lane's daemon in a ring with a second node nobody listens at."""
    from gubernator_tpu.utils.clock import Clock

    clock = Clock()
    clock.freeze(T0)
    daemon = _daemon(clock, native_ingress=True)
    daemon.set_peers([daemon.peer_info,
                      PeerInfo(grpc_address="127.0.0.1:9", http_address="127.0.0.1:9")])
    try:
        yield daemon
    finally:
        daemon.close()


def _keys_by_owner(daemon, name: str, count: int = 400) -> "tuple[list, list]":
    mine, theirs = [], []
    for i in range(count):
        key = f"{i}ring"
        (mine if daemon.service.get_peer(f"{name}_{key}").info.is_owner else theirs).append(key)
    assert len(mine) >= 4 and len(theirs) >= 4
    return mine, theirs


def _python_route(daemon, body: bytes) -> bytes:
    """What the daemon's own Python route answers `body` with the lane off
    (checks of no hit: the state is as it was afterwards)."""
    pump = daemon.gateway.pump
    pump.batcher.disable()
    try:
        return _post(_port(daemon), body)
    finally:
        pump.update_ring()


def test_in_a_two_node_ring_a_global_call_is_handed_to_the_python_router(two_nodes):
    daemon = two_nodes
    mine, _ = _keys_by_owner(daemon, "ring")
    plain = _body([_check("ring", mine[0], hits=0), _check("ring", mine[1], hits=0)])
    before = _lane_counts(daemon)
    kept = _post(_port(daemon), plain)  # keys this node owns, no flag: the lane's
    assert _lane_counts(daemon)["calls"] - before["calls"] == 1
    flagged = _body([_check("ring", mine[0], hits=0, behavior=GLOBAL), _check("ring", mine[1], hits=0)])
    before = _lane_counts(daemon)
    got = _post(_port(daemon), flagged)
    _handed_back(before, _lane_counts(daemon))
    assert got == _python_route(daemon, flagged)
    assert got == kept  # an owner's answer, whatever the routing bit says


def test_a_call_with_a_remotely_owned_key_is_handed_to_the_python_router(two_nodes):
    daemon = two_nodes
    mine, theirs = _keys_by_owner(daemon, "ring")
    body = _body([_check("ring", mine[2], hits=0), _check("ring", theirs[0], hits=0)])
    before = _lane_counts(daemon)
    got = _post(_port(daemon), body)
    _handed_back(before, _lane_counts(daemon))
    answer = json.loads(got.partition(b"\r\n\r\n")[2])["responses"]
    assert "error" not in answer[0] and answer[0]["remaining"] == "10"
    assert "error" in answer[1]  # nobody listens at the other node: Python's words for it
    assert json.loads(_python_route(daemon, body).partition(b"\r\n\r\n")[2])["responses"][0] == answer[0]


def _bare_lane(cap_lanes: int = 0):
    """A bare HttpEdge and IngressBatcher, a one-node ring pushed into it."""
    edge = native.HttpEdge("127.0.0.1:0")
    batcher = native.IngressBatcher()
    batcher.set_ring(np.zeros(0, np.uint64), np.zeros(0, np.uint8), all_self=True, enabled=True,
                     cap_lanes=cap_lanes, max_frame_lanes=16384, behavior_mask=0,
                     express_mask=NO_BATCHING)
    return edge, batcher


def _free(edge, batcher) -> None:
    batcher.stop()
    edge.shutdown()
    edge.free()
    batcher.free()


def test_the_shed_bound_answers_a_call_429_in_pythons_words():
    edge, batcher = _bare_lane(cap_lanes=3)
    s = socket.create_connection(("127.0.0.1", edge.port))
    s.settimeout(30.0)
    try:
        s.sendall(_head(_body([_check("shed", f"s{i}") for i in range(4)])))
        assert edge.next(timeout_ms=5000, ingress=batcher) is native.FAST_LANE  # shed IS native
        got = _read_answer(s)
        message = json.dumps({"code": 2, "message": IngressShedError(0, 3).message}).encode()
        assert got == (b"HTTP/1.1 429 Error\r\nContent-Type: application/json\r\nContent-Length: "
                       + str(len(message)).encode() + b"\r\n\r\n" + message)
        stats = batcher.stats()
        assert (stats["shedFrames"], stats["shedLanes"]) == (1, 4)
        assert (stats["calls"], stats["callFallbacks"], stats["pendingFrames"]) == (0, 0, 0)
    finally:
        s.close()
        _free(edge, batcher)


def test_a_stopping_lane_hands_a_call_back_and_answers_its_queued_calls_503():
    edge, batcher = _bare_lane()
    queued = socket.create_connection(("127.0.0.1", edge.port))
    late = socket.create_connection(("127.0.0.1", edge.port))
    try:
        body = _body([_check("stop", "q1"), _check("stop", "q2")])
        queued.settimeout(30.0)
        queued.sendall(_head(body))
        assert edge.next(timeout_ms=5000, ingress=batcher) is native.FAST_LANE
        assert batcher.stats()["pendingFrames"] == 1
        batcher.stop()  # SIGTERM's drain: what is queued is answered, nothing new is kept
        message = b'{"code": 14, "message": "shutting down"}'
        assert _read_answer(queued) == (
            b"HTTP/1.1 503 Error\r\nContent-Type: application/json\r\nContent-Length: "
            + str(len(message)).encode() + b"\r\n\r\n" + message)
        late.sendall(_head(body))
        token, method, path, raw, _ = edge.next(timeout_ms=5000, ingress=batcher)
        assert (method, path, raw) == ("POST", "/v1/GetRateLimits", body)  # whole, for Python
        stats = batcher.stats()
        assert (stats["calls"], stats["callFallbacks"], stats["pendingFrames"]) == (1, 1, 0)
        edge.respond(token, 503, message)
    finally:
        queued.close()
        late.close()
        _free(edge, batcher)


def test_the_take_reads_a_call_as_the_frame_of_its_checks():
    """What `gt_ingress_take` hands the pump for a call is what it hands it for
    the kind-5 frame of the same checks: columns, packed keys, ring hashes."""
    edge, batcher = _bare_lane()
    s = socket.create_connection(("127.0.0.1", edge.port))
    s.settimeout(30.0)
    try:
        checks = [_check("tk", "a1", hits=3, limit=7, duration=9_000),
                  _check("tk", "b22", hits=0, limit=70, duration=90_000, algorithm=1, behavior=RESET)]
        frame = _frame_of(checks)
        taken = []
        for body, ctype in ((_body(checks), "application/json"), (frame, wire.COLUMNS_CONTENT_TYPE)):
            s.sendall(_head(body, ctype))
            assert edge.next(timeout_ms=5000, ingress=batcher) is native.FAST_LANE
            tb = batcher.take(64, timeout_ms=5000)
            taken.append({
                "columns": [np.array(c) for c in (tb.algorithm, tb.behavior, tb.hits, tb.limit, tb.duration)],
                "keys": list(tb.hash_keys), "hashes": tb.hashes.tolist(),
                "names": [tb._name_at(i) for i in range(tb.n)], "uks": [tb._uk_at(i) for i in range(tb.n)],
                "beh_or": tb.beh_or, "hits_total": tb.hits_total, "calls": (tb.n_calls, tb.frame_call.tolist()),
                "sent": tb.frame_bytes(),
            })
            batcher.fail(tb, 500, "Error", "application/json", b"{}")
            _read_answer(s)
        call, framed = taken
        for a, b in zip(call["columns"], framed["columns"]):
            assert a.dtype == b.dtype and (a == b).all()
        for same in ("keys", "hashes", "names", "uks", "beh_or", "hits_total"):
            assert call[same] == framed[same], same
        assert call["keys"] == ["tk_a1", "tk_b22"] and call["hits_total"] == 3
        assert call["calls"] == (1, [1]) and framed["calls"] == (0, [0])
        assert call["sent"] == [] and framed["sent"] == [frame]  # the black box's: frames only
    finally:
        s.close()
        _free(edge, batcher)


# ---------------------------------------------------------------------
# (c) and (e): one take of a frame and three calls, and what it is booked as
# ---------------------------------------------------------------------

def _until(read, want, what: str) -> None:
    deadline = time.monotonic() + 30.0
    while read() != want:
        assert time.monotonic() < deadline, (what, read(), want)
        time.sleep(0.002)


def _sample(daemon, name: str, **labels) -> float:
    return daemon.service.metrics.registry.get_sample_value(name, labels) or 0.0


def _booked(daemon) -> dict:
    ring = daemon.service.blackbox.rings["public"]
    return {
        "ok": _sample(daemon, "gubernator_grpc_request_counts_total", status="0", method=RPC),
        "timed": _sample(daemon, "gubernator_grpc_request_duration_count", method=RPC),
        "hist": _sample(daemon, "gubernator_request_duration_seconds_count", method=RPC),
        "frames": _sample(daemon, "gubernator_ingress_columns_batches_total", encoding="frame"),
        "tapped": ring.stats()[2],
        "stats": daemon.gateway.pump.stats(),
    }


@pytest.fixture(scope="module")
def mixed_take(pair):
    """A 64-lane frame and three calls (one key of the frame's in each, so a
    wrong slice shows) queued behind a stalled pump, then let go: both pump
    threads sit on a primer call each at the depth semaphore meanwhile."""
    lane, _ = pair
    pump = lane.gateway.pump
    frame_checks = [_check("mix", f"{i}m", hits=1 + i % 4, limit=20, algorithm=i % 2) for i in range(64)]
    calls = [[_check("mix", f"{c}m", hits=2, limit=20, algorithm=c % 2), _check("mix", f"call{c}", hits=c + 1)]
             for c in range(3)]
    primers = [[_check("mix", f"primer{p}"), _check("mix", f"primer{p}b")] for p in range(2)]
    sent = [("frame", _frame_of(frame_checks), wire.COLUMNS_CONTENT_TYPE, frame_checks)] + [
        (f"call{c}", _body(checks), "application/json", checks) for c, checks in enumerate(calls)]
    before = _booked(lane)
    answers: dict = {}
    errors: list = []

    def client(tag, body, ctype):
        try:
            answers[tag] = _post(_port(lane), body, ctype)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = []
    for _ in range(NativeIngressPump.DEPTH):
        pump._sem.acquire()
    try:
        for p, checks in enumerate(primers):
            threads.append(threading.Thread(target=client, args=(f"primer{p}", _body(checks), "application/json")))
            threads[-1].start()
            _until(lambda: pump.stats()["batches"], before["stats"]["batches"] + p + 1, "primer taken")
        for i, (tag, body, ctype, _) in enumerate(sent):  # one after another: the queue's order
            threads.append(threading.Thread(target=client, args=(tag, body, ctype)))
            threads[-1].start()
            _until(lambda: pump.stats()["pendingFrames"], i + 1, "queued")
        queued = pump.stats()
    finally:
        for _ in range(NativeIngressPump.DEPTH):
            pump._sem.release()
    for t in threads:
        t.join(60.0)
    assert not errors, errors
    return {"lane": lane, "before": before, "after": _booked(lane), "queued": queued,
            "answers": answers, "sent": sent, "primers": primers}


def test_a_frame_and_three_calls_come_out_of_one_take(mixed_take):
    before, after, queued = (mixed_take[k] for k in ("before", "after", "queued"))
    assert (queued["pendingFrames"], queued["pendingLanes"]) == (4, 64 + 6)
    grown = {k: after["stats"][k] - before["stats"][k] for k in before["stats"]}
    assert grown["batches"] == 3  # a primer each, then ONE take of the four
    assert (grown["frames"], grown["calls"], grown["lanes"]) == (1, 3 + 2, 64 + 6 + 4)
    assert grown["fallbacks"] == grown["callFallbacks"] == 0


def test_each_of_a_mixed_take_is_answered_in_its_own_encoding_with_its_own_slice(mixed_take):
    answers, sent = mixed_take["answers"], mixed_take["sent"]
    cache = orc.OracleCache()
    # One take, one clock reading: its lanes in the queue's order.
    head, _, body = answers["frame"].partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 200 OK\r\nContent-Type: application/x-gubernator-columns\r\n")
    got = wire.decode_ingress_result_frame(body)
    rows = [orc.apply(cache, r, T0) for r in sent[0][3]]
    assert got.status.tolist() == [int(r.status) for r in rows]
    assert got.remaining.tolist() == [r.remaining for r in rows]
    assert got.reset_time.tolist() == [r.reset_time for r in rows]
    assert got.limit.tolist() == [r.limit for r in rows]
    for tag, _, _, checks in sent[1:]:
        assert answers[tag] == _oracle_answer(cache, checks), tag
    for p, checks in enumerate(mixed_take["primers"]):
        assert answers[f"primer{p}"] == _oracle_answer(orc.OracleCache(), checks)


def test_a_call_is_counted_and_timed_once_and_is_no_columnar_frame(mixed_take):
    before, after = mixed_take["before"], mixed_take["after"]
    requests = 1 + 3 + 2  # the frame, the calls, the primers
    assert after["ok"] - before["ok"] == requests
    assert after["timed"] - before["timed"] == requests
    assert after["hist"] - before["hist"] == requests
    assert after["frames"] - before["frames"] == 1  # encoding="frame": the frame alone


def test_the_black_box_holds_the_takes_frame_and_none_of_its_calls(mixed_take):
    lane, before, after = (mixed_take[k] for k in ("lane", "before", "after"))
    assert after["tapped"] - before["tapped"] == 1
    record = lane.service.blackbox.rings["public"].freeze()[-1]
    assert record[2:5] == ("in", "", 5) and record[5] == mixed_take["sent"][0][1]
    # What `BlackBox.tap` does with a JSON body on the Python route: nothing.
    tapped = lane.service.blackbox.rings["public"].stats()[2]
    lane.service.blackbox.tap("in", "", mixed_take["sent"][1][1])
    assert lane.service.blackbox.rings["public"].stats()[2] == tapped


def test_the_audit_stays_clean_and_the_status_page_shows_both_counts(mixed_take):
    lane = mixed_take["lane"]
    assert _get(lane, "/debug/audit")["violationTotal"] == 0
    stats = lane.gateway.pump.stats()
    ingress = _get(lane, "/debug/status")["ingress"]
    assert (ingress["calls"], ingress["callFallbacks"]) == (stats["calls"], stats["callFallbacks"])
    with urllib.request.urlopen(f"http://127.0.0.1:{_port(lane)}/metrics", timeout=30) as r:
        text = r.read().decode()
    for stat in ("calls", "callFallbacks", "frames", "fallbacks"):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith('gubernator_native_ingress_batches_total{stat="%s"}' % stat))
        assert float(line.split()[-1]) == stats[stat], line


# ---------------------------------------------------------------------
# (d) NO_BATCHING rides the express queue
# ---------------------------------------------------------------------

@pytest.mark.parametrize("flagged", [1, 2], ids=["one_lane", "both_lanes"])
def test_a_no_batching_call_rides_the_express_queue(pair, flagged):
    lane, python = pair
    checks = [_check("xp", f"x{flagged}a", behavior=NO_BATCHING),
              _check("xp", f"x{flagged}b", behavior=NO_BATCHING if flagged == 2 else 0)]
    body = _body(checks)
    before = lane.gateway.pump.stats()
    got = _post(_port(lane), body)
    after = lane.gateway.pump.stats()
    assert after["expressFrames"] - before["expressFrames"] == 1  # the call, whole
    assert after["expressLanes"] - before["expressLanes"] == 2
    assert after["calls"] - before["calls"] == 1 and after["callFallbacks"] == before["callFallbacks"]
    assert got == _post(_port(python), body) == _oracle_answer(orc.OracleCache(), checks)
    plain = _body([_check("xp", f"x{flagged}c"), _check("xp", f"x{flagged}d")])
    _post(_port(lane), plain)
    assert lane.gateway.pump.stats()["expressFrames"] == after["expressFrames"]  # a plain call: the bulk queue


# ---------------------------------------------------------------------
# The benchmark's reading of it: chipbench/layer_metrics/ingress.native_call_share.json
# ---------------------------------------------------------------------

METRIC = "ingress.native_call_share"
COUNTER = "gubernator_native_ingress_batches_total"


def _metric_spec():
    from chipbench import harness

    return harness, harness.load_json(harness.BENCH_DIR, "layer_metrics", METRIC + ".json")


def _read_share(before: list, after: list):
    from chipbench.readers import counter_share

    _, spec = _metric_spec()
    assert spec["reader"] == "counter_share"
    return counter_share.read(
        {"before": {"metrics": before}, "after": {"metrics": after}}, spec["params"])


def test_the_call_share_metric_loads_under_the_harness_and_is_the_singles_cells():
    harness, spec = _metric_spec()
    bench = harness.load_json(harness.REPO, "BENCHMARK.json")
    entry = bench["per_layer"][45]  # appended by PR 47, nothing before it moved (PR 48's six follow)
    assert entry["name"] == spec["name"] == METRIC
    assert entry["workloads"] == ["v5e1-1m.singles"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert (entry["unit"], entry["layer"], entry["moves"]) == ("%", "L6 edge", "req_p50_ms")
    assert spec["params"] == {"metric": COUNTER, "numerator": ['"calls"'],
                              "others": ['"callFallbacks"'], "times": 100}
    cells = {w["name"]: w for w in bench["workloads"]}
    applies = [name for name, cell in cells.items() if harness.metric_applies(entry, cell, bench)]
    assert applies == ["v5e1-1m.singles"]
    # The accepted twin reads what it read: frames and fallbacks, not calls.
    twin = harness.load_json(harness.BENCH_DIR, "layer_metrics", "ingress.native_frame_share.json")
    assert twin["params"]["numerator"] == ['"frames"'] and twin["params"]["others"] == ['"fallbacks"']


def test_counter_share_reads_100_from_a_daemon_that_served_classic_calls(pair):
    from chipbench.daemon import Http, metric_sum

    lane, _ = pair
    http = Http(f"127.0.0.1:{_port(lane)}")
    try:
        before = http.scrape()
        for i in range(5):
            _post(_port(lane), _body([_check("share", f"s{i}"), _check("share", f"t{i}")]))
        _post(_port(lane), _frame_of([_check("share", "framed")]), wire.COLUMNS_CONTENT_TYPE)
        kept = http.scrape()
        assert _read_share(before, kept) == 100.0  # a frame counts under `frames`, not here
        assert metric_sum(kept, COUNTER, '"calls"') - metric_sum(before, COUNTER, '"calls"') == 5
        assert metric_sum(kept, COUNTER, '"frames"') - metric_sum(before, COUNTER, '"frames"') == 1
        _post(_port(lane), FALLBACK_BODIES["a_float"])
        assert _read_share(before, http.scrape()) == pytest.approx(100 * 5 / 6)
        same = http.scrape()
        assert _read_share(same, same) is None  # nothing between the scrapes
    finally:
        http.close()


def test_counter_share_reads_nothing_from_scrapes_without_the_sample(pair):
    from chipbench.daemon import Http

    _, python = pair
    def parent(frames: int, fallbacks: int) -> list:
        """A scrape of a program from before PR 47: no sample of calls."""
        return [(COUNTER, '{stat="frames"}', float(frames)), (COUNTER, '{stat="fallbacks"}', float(fallbacks)),
                (COUNTER, '{stat="lanes"}', 64.0 * frames), (COUNTER, '{stat="batches"}', float(frames))]

    assert _read_share(parent(15_626, 0), parent(15_700, 3)) is None
    assert _read_share([], []) is None
    # And a daemon that runs no pump at all exports no sample of the counter.
    http = Http(f"127.0.0.1:{_port(python)}")
    try:
        before = http.scrape()
        _post(_port(python), _body([_check("share", "p1"), _check("share", "p2")]))
        after = http.scrape()
    finally:
        http.close()
    assert not [row for row in after if row[0] == COUNTER]
    assert _read_share(before, after) is None
