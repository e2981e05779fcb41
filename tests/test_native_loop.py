"""Native service loop (host_runtime.cpp gt_ingress_* + the
multi-acceptor epoll edge): fast-lane end-to-end oracle + byte-identity
with the PR 8 Python-assembled edge, the same-host UDS lane,
adversarial byte-fuzz of the native frame parser on both transports,
REUSEPORT acceptor fairness, the adaptive idle timeout, native route
parity with hash_ring, and native-shed wording parity."""

from __future__ import annotations

import json
import os
import random
import resource
import socket
import struct
import threading
import time

import numpy as np
import pytest

from gubernator_tpu import native, wire
from gubernator_tpu.client import ColumnsV1Client, V1Client
from gubernator_tpu.cluster import fast_test_behaviors
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.daemon import Daemon
from gubernator_tpu.parallel.hash_ring import ReplicatedConsistentHash
from gubernator_tpu.service import IngressShedError
from gubernator_tpu.types import SECOND, Behavior
from gubernator_tpu.utils.clock import Clock

T0 = 1_573_430_400_000

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native runtime unavailable"
)


def _standalone(clock, *, native_ingress: bool, acceptors: int = 1,
                uds_path: str = "") -> Daemon:
    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0
    behaviors.multi_region_sync_wait_s = 3600.0
    behaviors.native_ingress = native_ingress
    d = Daemon(
        DaemonConfig(
            listen_address="127.0.0.1:0",
            grpc_listen_address="127.0.0.1:0",
            cache_size=4096,
            global_cache_size=256,
            behaviors=behaviors,
            peer_discovery_type="static",
            native_http=True,
            acceptors=acceptors,
            uds_path=uds_path,
        ),
        clock=clock,
    ).start()
    d.set_peers([d.peer_info])
    return d


@pytest.fixture(scope="module")
def daemons(tmp_path_factory):
    """One native-loop daemon (2 acceptors + a UDS lane) and one
    GUBER_NATIVE_INGRESS=0 daemon — exactly the PR 8 edge — sharing a
    frozen clock, so the two must answer the same frames with the same
    bytes."""
    clock = Clock()
    clock.freeze(T0)
    sock = str(tmp_path_factory.mktemp("uds") / "gub.sock")
    fast = _standalone(clock, native_ingress=True, acceptors=2,
                       uds_path=sock)
    pr8 = _standalone(clock, native_ingress=False)
    yield fast, pr8, clock, sock
    fast.close()
    pr8.close()


def _frame(name, keys, hits=1, limit=1000, duration=3_600_000, algo=0,
           behavior=0):
    n = len(keys)
    return wire.encode_ingress_frame((
        [name] * n, list(keys),
        np.full(n, algo, np.int32), np.full(n, behavior, np.int32),
        np.full(n, hits, np.int64), np.full(n, limit, np.int64),
        np.full(n, duration, np.int64),
    ))


def _connect(target):
    if isinstance(target, str):
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.connect(target)
    else:
        s = socket.create_connection(("127.0.0.1", target))
    s.settimeout(30.0)
    return s


def _post_raw(sock, body,
              ctype=wire.COLUMNS_CONTENT_TYPE) -> "tuple[bytes, bytes]":
    """One POST /v1/GetRateLimits on an open socket; returns the raw
    (full response bytes, body bytes)."""
    head = (
        f"POST /v1/GetRateLimits HTTP/1.1\r\nHost: t\r\n"
        f"Content-Type: {ctype}\r\nContent-Length: {len(body)}\r\n\r\n"
    ).encode()
    sock.sendall(head + body)
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("server closed mid-response")
        buf += chunk
    hdr, _, rest = buf.partition(b"\r\n\r\n")
    clen = 0
    for line in hdr.split(b"\r\n"):
        if line.lower().startswith(b"content-length"):
            clen = int(line.split(b":")[1])
    while len(rest) < clen:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("server closed mid-body")
        rest += chunk
    return hdr + b"\r\n\r\n" + rest[:clen], rest[:clen]


def _post(target, body, **kw):
    s = _connect(target)
    try:
        return _post_raw(s, body, **kw)
    finally:
        s.close()


# ---------------------------------------------------------------------
# fast lane end to end + byte identity with the PR 8 edge
# ---------------------------------------------------------------------

def test_fast_lane_serves_frames_natively(daemons):
    fast, _pr8, _clock, _sock = daemons
    before = fast.gateway.pump.stats()
    raw, body = _post(fast.gateway._edge.port,
                      _frame("nl", [f"fast{i}" for i in range(16)]))
    assert raw.startswith(b"HTTP/1.1 200 OK")
    rc = wire.decode_ingress_result_frame(body)
    assert rc.n == 16
    assert (np.asarray(rc.remaining) == 999).all()
    after = fast.gateway.pump.stats()
    assert after["frames"] == before["frames"] + 1
    assert after["lanes"] == before["lanes"] + 16


def test_pump_folds_natively_and_sums_exactly(daemons):
    """A real NativeIngressPump: the audit's `ingress_hits` (summed in
    gt_ingress_take), the tenant ledger's `totals["hits"]` (summed by
    the native fold) and the hits sent agree exactly, every take folds
    once into each sketch, and /debug/status `folds` says the native
    pass ran them and how few candidates Python touched."""
    import urllib.request

    from gubernator_tpu import audit as audit_mod

    fast, _pr8, _clock, _sock = daemons
    port = fast.gateway._edge.port

    def status():
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/status", timeout=30
        ) as r:
            return json.loads(r.read())

    before = status()["folds"]
    assert set(before) == {"native", "keyCandidates", "tenantCandidates"}
    assert before["native"] is True  # the pump exists: so does the library
    folds0 = (fast.service.hotkeys.batches, fast.service.tenants.batches)
    pump0 = fast.gateway.pump.stats()
    audit0 = audit_mod.ledger_snapshot().get("ingress_hits", 0)
    led0 = fast.service.tenants.totals()
    sent_hits = sent_lanes = 0
    for t, hits in enumerate((0, 1, 7, 3)):
        n = 40 + t  # above the ledger's topk: the batch fold, not _fold_few
        raw, body = _post(port, _frame(
            f"folds-{t % 2}", [f"fold{t}-{i % 30}" for i in range(n)],
            hits=hits, limit=10**9,
        ))
        assert raw.startswith(b"HTTP/1.1 200 OK")
        assert wire.decode_ingress_result_frame(body).n == n
        sent_hits += hits * n
        sent_lanes += n
    pump1 = fast.gateway.pump.stats()
    takes = pump1["batches"] - pump0["batches"]
    assert takes == 4 and pump1["lanes"] - pump0["lanes"] == sent_lanes
    assert (
        audit_mod.ledger_snapshot().get("ingress_hits", 0) - audit0
        == sent_hits
    )
    led1 = fast.service.tenants.totals()
    assert led1["hits"] - led0["hits"] == sent_hits == 464
    assert led1["lanes"] - led0["lanes"] == sent_lanes
    after = status()["folds"]
    assert (fast.service.hotkeys.batches - folds0[0],
            fast.service.tenants.batches - folds0[1]) == (takes, takes)
    # Python's share of a take: the candidates, never the lanes.
    for plane in ("keyCandidates", "tenantCandidates"):
        assert 0 <= after[plane] - before[plane] <= 16 * takes
    assert after["keyCandidates"] > before["keyCandidates"]
    snap = fast.service.tenants.snapshot()
    for stat in ("hits", "lanes", "ingressBytes"):
        assert (
            sum(r[stat] for r in snap["topk"]) + snap["other"][stat]
            == snap["totals"][stat]
        )


def test_fast_lane_byte_identical_to_python_edge(daemons):
    """The knob-off interop line: the native loop's kind-6 fill (and
    its HTTP envelope) must be byte-identical to the PR 8
    Python-assembled response for the same frame against the same
    frozen-clock state."""
    fast, pr8, _clock, _sock = daemons
    for frame in (
        _frame("ident", [f"b{i}" for i in range(9)]),
        _frame("ident", [f"b{i}" for i in range(9)], hits=3, limit=5),
        _frame("ident", ["dup", "dup", "dup"], limit=2),
        _frame("ident", [f"l{i}" for i in range(4)], algo=1, limit=7),
    ):
        raw_fast, _ = _post(fast.gateway._edge.port, frame)
        raw_pr8, _ = _post(pr8.gateway._edge.port, frame)
        assert raw_fast == raw_pr8
    assert fast.gateway.pump.stats()["frames"] >= 3  # dup frame may round


def test_classic_json_clients_untouched(daemons):
    """GUBER_ACCEPTORS>1 + the fast lane must leave plain JSON clients
    byte-identical to the PR 8 edge."""
    fast, pr8, _clock, _sock = daemons
    body = json.dumps({
        "requests": [
            {"name": "cj", "uniqueKey": f"k{i}", "hits": "1",
             "limit": "10", "duration": "60000"}
            for i in range(5)
        ]
    }).encode()
    raw_fast, body_fast = _post(fast.gateway._edge.port, body,
                                ctype="application/json")
    raw_pr8, body_pr8 = _post(pr8.gateway._edge.port, body,
                              ctype="application/json")
    assert raw_fast == raw_pr8
    assert json.loads(body_fast) == json.loads(body_pr8)


def test_global_lanes_of_a_one_node_ring_stay_native(daemons):
    """A GLOBAL lane needs the replica path only where another daemon may
    own its key.  In a one-node ring it is the owner's own: the native
    submit keeps the frame (since PR 41; tests/test_mixed_cell.py holds
    the two-node ring, where it still falls back), the pump does the
    owner's book-keeping, and the answer is the Python edge's."""
    fast, pr8, _clock, _sock = daemons
    before = fast.gateway.pump.stats()
    frame = _frame("gl", ["g1", "g2", "g1"], behavior=int(Behavior.GLOBAL))
    raw, body = _post(fast.gateway._edge.port, frame)
    assert raw.startswith(b"HTTP/1.1 200 OK")
    rc = wire.decode_ingress_result_frame(body)
    assert rc.n == 3 and rc.remaining.tolist() == [999, 999, 998] and not rc.overrides
    after = fast.gateway.pump.stats()
    assert after["fallbacks"] == before["fallbacks"]
    assert after["frames"] == before["frames"] + 1
    table = fast.service.store.gtable
    assert table.get("gl_g1") is not None and table.get("gl_g2") is not None
    # The PR 8 edge (no native lane) answers the same numbers.
    _, body_pr8 = _post(pr8.gateway._edge.port, frame)
    rc8 = wire.decode_ingress_result_frame(body_pr8)
    assert (rc8.status.tolist(), rc8.remaining.tolist()) == (rc.status.tolist(), rc.remaining.tolist())


def test_validation_error_lanes_fall_back_with_exact_wording(daemons):
    fast, pr8, _clock, _sock = daemons
    n = 3
    frame = wire.encode_ingress_frame((
        ["v", "", "v"], ["a", "b", ""],
        np.zeros(n, np.int32), np.zeros(n, np.int32),
        np.ones(n, np.int64), np.full(n, 10, np.int64),
        np.full(n, 60_000, np.int64),
    ))
    raw_fast, body = _post(fast.gateway._edge.port, frame)
    raw_pr8, _ = _post(pr8.gateway._edge.port, frame)
    assert raw_fast == raw_pr8
    rc = wire.decode_ingress_result_frame(body)
    assert rc.overrides[1].error == "field 'namespace' cannot be empty"
    assert rc.overrides[2].error == "field 'unique_key' cannot be empty"


# ---------------------------------------------------------------------
# same-host UDS lane
# ---------------------------------------------------------------------

def test_uds_end_to_end_oracle_vs_tcp(daemons):
    """The UDS lane must serve the same kind-5/6 protocol: a fresh key
    sequence over UDS behaves exactly like its twin over TCP (limit
    algebra + OVER_LIMIT), and the raw response bytes match lane for
    lane."""
    fast, _pr8, _clock, sock = daemons
    port = fast.gateway._edge.port
    for i in range(4):
        f_tcp = _frame("udso", [f"tcp{i}"], limit=2)
        f_uds = _frame("udso", [f"uds{i}"], limit=2)
        raw_t, body_t = _post(port, f_tcp)
        raw_u, body_u = _post(sock, f_uds)
        rt = wire.decode_ingress_result_frame(body_t)
        ru = wire.decode_ingress_result_frame(body_u)
        assert list(rt.remaining) == list(ru.remaining)
        assert list(rt.status) == list(ru.status)
    # Hit one UDS key to exhaustion: OVER_LIMIT must appear exactly
    # like on TCP.
    statuses = []
    for _ in range(4):
        _, body = _post(sock, _frame("udso", ["burn"], limit=2))
        rc = wire.decode_ingress_result_frame(body)
        statuses.append(int(rc.status[0]))
    assert statuses == [0, 0, 1, 1]


def test_columns_client_speaks_unix_target(daemons):
    fast, _pr8, _clock, sock = daemons
    client = ColumnsV1Client(f"unix://{sock}", timeout_s=15.0)
    try:
        resp = client.check("udsc", "k1", hits=1, limit=5,
                            duration=60_000).result(timeout=15)
        assert resp.remaining == 4
        assert client.health_check().status == "healthy"
    finally:
        client.close()
    # The classic client also speaks unix:// (health/metrics surface).
    v1 = V1Client(f"unix://{sock}", timeout_s=15.0)
    try:
        assert v1.health_check().status == "healthy"
    finally:
        v1.close()


# ---------------------------------------------------------------------
# adversarial byte-fuzz of the native frame parser (TCP and UDS edges)
# ---------------------------------------------------------------------

def _mutations(rng, frame: bytes):
    """Seeded adversarial mutations: truncations, non-monotone string
    offsets, overflow column lengths, bad UTF-8, garbage flips."""
    yield frame[:9]                      # shorter than the header
    yield frame[:rng.randrange(10, len(frame))]          # truncated body
    yield frame + b"X"                   # trailing garbage
    mut = bytearray(frame)
    mut[14:18], mut[18:22] = mut[18:22], mut[14:18]  # offsets swap
    yield bytes(mut)
    mut = bytearray(frame)
    struct.pack_into("<I", mut, 10, 0x7FFFFFFF)  # name blob len overflow
    yield bytes(mut)
    mut = bytearray(frame)
    struct.pack_into("<I", mut, 6, 2**31 - 1)    # absurd lane count
    yield bytes(mut)
    # bad UTF-8 inside the name blob (keeps lengths/offsets valid)
    mut = bytearray(frame)
    n = struct.unpack_from("<I", frame, 6)[0]
    blob_pos = 10 + 4 + 4 * (n + 1)
    mut[blob_pos] = 0xFF
    yield bytes(mut)
    for _ in range(12):
        mut = bytearray(frame)
        for _ in range(rng.randrange(1, 8)):
            mut[rng.randrange(len(mut))] = rng.randrange(256)
        yield bytes(mut)


@pytest.mark.parametrize("transport", ["tcp", "uds"])
def test_fuzzed_frames_never_crash_and_400_with_reason(daemons, transport):
    fast, _pr8, _clock, sock = daemons
    target = fast.gateway._edge.port if transport == "tcp" else sock
    rng = random.Random(0xC0FFEE if transport == "tcp" else 0xBEEF)
    base = _frame("fz", [f"k{i}" for i in range(6)], limit=50)
    for mut in _mutations(rng, base):
        raw, body = _post(target, mut)
        status = int(raw.split(b" ", 2)[1])
        # Every mutation answers: a clean 200 (the flips that happen to
        # stay valid) or a reasoned 4xx — never a hang, reset or 5xx.
        assert status in (200, 400), (status, body[:120], mut[:40].hex())
        if status == 400:
            msg = json.loads(body)
            assert msg["message"], msg
    # The daemon survived with full service: a clean frame still works.
    _, body = _post(target, _frame("fz", [f"alive-{transport}"], limit=50))
    rc = wire.decode_ingress_result_frame(body)
    assert int(rc.remaining[0]) == 49
    assert fast.service.health_check().status == "healthy"


# ---------------------------------------------------------------------
# REUSEPORT acceptor fairness + per-acceptor counters
# ---------------------------------------------------------------------

def test_acceptor_fairness_under_concurrent_clients(daemons):
    """16 concurrent pipelined clients over the 2-acceptor REUSEPORT
    group: every TCP acceptor must see connections and requests (the
    kernel shards by 4-tuple), the per-acceptor counters must be
    populated, and every response must decode clean."""
    fast, _pr8, _clock, _sock = daemons
    port = fast.gateway._edge.port
    before = {
        i: r for i, r in enumerate(fast.gateway._edge.acceptor_stats())
    }
    errors = []

    def one(t):
        try:
            s = _connect(port)
            try:
                for j in range(3):
                    _, body = _post_raw(
                        s, _frame("fair", [f"t{t}j{j}l{i}" for i in range(8)])
                    )
                    rc = wire.decode_ingress_result_frame(body)
                    assert rc.n == 8
            finally:
                s.close()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=one, args=(t,)) for t in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    rows = fast.gateway._edge.acceptor_stats()
    tcp_rows = [r for r in rows if not r["uds"]]
    assert len(tcp_rows) == 2
    for i, row in enumerate(tcp_rows):
        assert row["accepted"] > before[i]["accepted"], rows
        assert row["requests"] > before[i]["requests"], rows
    # The fast lane consumed the frames (not the Python path): lanes
    # counters advanced across the group.
    assert sum(r["ingressLanes"] for r in tcp_rows) >= sum(
        before[i]["ingressLanes"] for i in range(2)
    ) + 16 * 3 * 8


def test_acceptor_metrics_exported(daemons):
    fast, _pr8, _clock, _sock = daemons
    v1 = V1Client(f"127.0.0.1:{fast.gateway._edge.port}", timeout_s=15.0)
    try:
        text = v1.metrics_text()
    finally:
        v1.close()
    assert 'gubernator_ingress_acceptor_requests{acceptor="0",transport="tcp"}' in text
    assert 'gubernator_ingress_acceptor_requests{acceptor="1",transport="tcp"}' in text
    assert 'transport="uds"' in text
    assert 'gubernator_native_ingress_batches_total{stat="lanes"}' in text


# ---------------------------------------------------------------------
# adaptive idle timeout (satellite: no fixed-tick burn per acceptor)
# ---------------------------------------------------------------------

def test_idle_acceptors_block_without_wakeups():
    """An idle edge must not tick: with the adaptive timeout the epoll
    loops block indefinitely (wakeup counters frozen) and the process
    burns ~no CPU while idle; a request afterwards still answers
    (the eventfd wake path)."""
    edge = native.HttpEdge("127.0.0.1:0", acceptors=3)
    try:
        time.sleep(0.2)  # accept-queue settle
        w0 = [r["wakeups"] for r in edge.acceptor_stats()]
        cpu0 = resource.getrusage(resource.RUSAGE_SELF)
        t0 = time.monotonic()
        time.sleep(0.6)
        w1 = [r["wakeups"] for r in edge.acceptor_stats()]
        cpu1 = resource.getrusage(resource.RUSAGE_SELF)
        elapsed = time.monotonic() - t0
        assert w1 == w0, f"idle acceptors woke: {w0} -> {w1}"
        burn = (cpu1.ru_utime - cpu0.ru_utime) + (
            cpu1.ru_stime - cpu0.ru_stime
        )
        # Not a tight bound (other threads of the test process run),
        # just proof there is no per-acceptor busy tick.
        assert burn < 0.5 * elapsed, f"idle CPU {burn:.3f}s over {elapsed:.3f}s"
        # Liveness after the indefinite block: accept + respond works.
        s = _connect(edge.port)
        try:
            s.sendall(b"GET /x HTTP/1.1\r\nHost: t\r\nContent-Length: 0\r\n\r\n")
            got = edge.next(timeout_ms=2000)
            assert got is not None and got[2] == "/x"
            edge.respond(got[0], 200, b"{}")
            raw, _ = _read_response(s)
            assert raw.startswith(b"HTTP/1.1 200")
        finally:
            s.close()
    finally:
        edge.shutdown()
        edge.free()


def _read_response(sock):
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            break
        buf += chunk
    hdr, _, rest = buf.partition(b"\r\n\r\n")
    clen = 0
    for line in hdr.split(b"\r\n"):
        if line.lower().startswith(b"content-length"):
            clen = int(line.split(b":")[1])
    while len(rest) < clen:
        rest += sock.recv(65536)
    return hdr + b"\r\n\r\n" + rest, rest


# ---------------------------------------------------------------------
# native route + shed parity units (bare edge + batcher, no daemon)
# ---------------------------------------------------------------------

def _edge_with_batcher(ring_peers, self_id, cap_lanes=0):
    """Bare HttpEdge + IngressBatcher with a ring snapshot computed
    EXACTLY the way NativeIngressPump.update_ring does, from a real
    ReplicatedConsistentHash."""
    edge = native.HttpEdge("127.0.0.1:0")
    b = native.IngressBatcher()
    ring = ReplicatedConsistentHash()
    for pid in ring_peers:
        ring.add(pid)
    codes = np.asarray(ring._vnode_code, dtype=np.int32)
    self_codes = [c for c, pid in enumerate(ring._code_ids)
                  if pid == self_id]
    vself = np.isin(codes, np.asarray(self_codes, np.int32)).astype(np.uint8)
    b.set_ring(
        np.asarray(ring._vnode_hashes, np.uint64), vself,
        all_self=len(ring_peers) == 1 and ring_peers[0] == self_id,
        enabled=True, cap_lanes=cap_lanes, max_frame_lanes=16384,
        behavior_mask=1 | 2 | 4 | 16,
    )
    return edge, b, ring


def test_native_route_matches_hash_ring():
    """The C++ searchsorted route must agree with
    hash_ring.get_batch_codes lane for lane: frames whose keys all map
    to self enqueue; frames with any remote-owned lane fall back."""
    edge, b, ring = _edge_with_batcher(["peerA", "peerB"], "peerA")
    try:
        # Index-FIRST keys: FNV-1 clusters suffix-varying keys onto one
        # vnode run (the documented test_hash_ring finding).
        keys = [f"{i}route" for i in range(64)]
        codes, ids = ring.get_batch_codes([f"rt_{k}" for k in keys])
        owner_is_a = np.asarray(
            [ids[c] == "peerA" for c in codes], dtype=bool
        )
        mine = [k for k, m in zip(keys, owner_is_a) if m]
        theirs = [k for k, m in zip(keys, owner_is_a) if not m]
        assert mine and theirs  # both classes present at 64 keys
        s = _connect(edge.port)
        try:
            # All-mine frame: consumed natively (worker returns FAST_LANE).
            s.sendall(_http_post(_frame("rt", mine)))
            got = edge.next(timeout_ms=2000, ingress=b)
            assert got is native.FAST_LANE
            tb = b.take(65536, timeout_ms=2000)
            assert tb is not None and tb.n == len(mine)
            # The hashes the native route computed match fnv1_batch.
            expect = native.fnv1_batch([f"rt_{k}" for k in mine])
            assert (tb.hashes == expect).all()
            b.fail(tb, 500, "Error", "application/json", b"{}")
            _read_response(s)
            # Any-remote frame: falls back to the Python path.
            s.sendall(_http_post(_frame("rt", [mine[0], theirs[0]])))
            got = edge.next(timeout_ms=2000, ingress=b)
            assert got is not native.FAST_LANE and got is not None
            assert b.stats()["fallbacks"] == 1
            edge.respond(got[0], 200, b"{}")
            _read_response(s)
        finally:
            s.close()
    finally:
        b.stop()
        edge.shutdown()
        edge.free()
        b.free()


def _http_post(body):
    return (
        f"POST /v1/GetRateLimits HTTP/1.1\r\nHost: t\r\nContent-Type: "
        f"{wire.COLUMNS_CONTENT_TYPE}\r\nContent-Length: {len(body)}\r\n\r\n"
    ).encode() + body


def test_native_shed_matches_python_wording():
    """The native 429 must be byte-identical to the Python
    IngressShedError triplet (code 2, same message, same status) so
    clients cannot tell which tier declined."""
    edge, b, _ring = _edge_with_batcher(["me"], "me", cap_lanes=100)
    try:
        s = _connect(edge.port)
        try:
            s.sendall(_http_post(_frame("shed", [f"s{i}" for i in range(200)])))
            got = edge.next(timeout_ms=2000, ingress=b)
            assert got is native.FAST_LANE  # handled: shed IS native
            raw, body = _read_response(s)
            assert raw.startswith(b"HTTP/1.1 429")
            exc = IngressShedError(0, 100)
            assert json.loads(body) == {"code": 2, "message": exc.message}
            stats = b.stats()
            assert stats["shedFrames"] == 1 and stats["shedLanes"] == 200
        finally:
            s.close()
    finally:
        b.stop()
        edge.shutdown()
        edge.free()
        b.free()


def test_reshard_window_disables_fast_lane(daemons):
    """A membership change with an open double-dispatch window must
    turn the fast lane off (moved keys owe the old owner a peek only
    the Python router performs) and re-enable after the window."""
    fast, _pr8, _clock, _sock = daemons
    pump = fast.gateway.pump
    svc = fast.service
    try:
        with svc._peer_mutex:
            svc._prev_picker = svc.local_picker
            svc._handoff_deadline = time.monotonic() + 0.4
        pump.update_ring()
        before = pump.stats()["fallbacks"]
        raw, _body = _post(fast.gateway._edge.port,
                           _frame("rw", ["w1", "w2"]))
        assert raw.startswith(b"HTTP/1.1 200 OK")
        assert pump.stats()["fallbacks"] > before  # Python path served it
        # After the deadline the pump loop re-pushes enabled.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            frames0 = pump.stats()["frames"]
            raw, _body = _post(fast.gateway._edge.port,
                               _frame("rw", [f"w3{time.monotonic()}"]))
            if pump.stats()["frames"] > frames0:
                break
            time.sleep(0.05)
        else:
            pytest.fail("fast lane never re-enabled after the window")
    finally:
        with svc._peer_mutex:
            svc._prev_picker = None
            svc._handoff_deadline = 0.0
        pump.update_ring()
