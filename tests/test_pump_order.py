"""The order of a native take's duties (gateway.NativeIngressPump): between
`gt_ingress_take`'s return and the launch stands only what the answer needs;
what only observes the take (the ring's counters, the black-box tap, the tenant
fold, the hot-key sketch) runs once it has launched and is done before a client
has its answer, on the path that succeeds and on the two that fail (the
dispatch raises at its launch; the answer raises when it is waited for).

One served daemon on ONE device, a frozen clock, `store.apply_columns_async`
wrapped to record what has run.  Cases: {one frame, three frames a take} x
{plain, one GLOBAL lane, one MULTI_REGION lane, every lane calendar} x the three
outcomes.  Held in each: the order; the ledger's totals, `hotkeys.batches`, the
black box's `frames_total` and its records (the bytes sent), the audit's
counters and the answers, all as a take that ran its observers in front of the
launch gave them; `beh_or` and `/debug/status` `ingress.plainTakes` read what the
frames held; `/debug/latency` counts exactly one `pump.admit`, one
`calendar.resolve` and one `behavior.handle` a take (the metrics' divisors).

A take of three frames is made by holding the pump's depth semaphore: each of
the two pump threads then sits on a take of one primer frame, the three frames
queue behind them and the next take holds all three."""

from __future__ import annotations

import json
import socket
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from gubernator_tpu import audit as audit_mod
from gubernator_tpu import native, saturation, wire
from gubernator_tpu.gateway import NativeIngressPump
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest

from . import oracle as orc

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the native ingress lane needs the host runtime")

T0 = 1_790_000_000_000
LANES = 7  # a frame's; a primer's are 2 and 3, so a take is known by its lanes
GREG_HOURS = 1
GLOBAL, MULTI_REGION, CALENDAR = (
    int(Behavior.GLOBAL), int(Behavior.MULTI_REGION), int(Behavior.DURATION_IS_GREGORIAN))
KINDS = {"plain": 0, "global": GLOBAL, "multi_region": MULTI_REGION, "calendar": CALENDAR}
OBSERVERS = ("stats", "tap", "fold", "sketch")


@pytest.fixture(scope="module")
def served():
    from gubernator_tpu.cluster import fast_test_behaviors
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import Daemon
    from gubernator_tpu.utils.clock import Clock

    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0
    behaviors.multi_region_sync_wait_s = 3600.0
    clock = Clock()
    clock.freeze(T0)
    saturation.reset()
    daemon = Daemon(DaemonConfig(
        listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0", cache_size=4096,
        global_cache_size=256, behaviors=behaviors, peer_discovery_type="static",
        native_http=True, devices=jax.devices()[:1], warmup_shapes=[]), clock=clock).start()
    daemon.set_peers([daemon.peer_info])
    try:
        yield daemon
    finally:
        daemon.close()
        saturation.reset()


def _get(daemon, path: str) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{daemon.gateway._edge.port}{path}", timeout=30) as r:
        return json.loads(r.read())


def _post(port: int, frame: bytes) -> "tuple[int, bytes]":
    """(HTTP status, body) of one POST /v1/GetRateLimits."""
    s = socket.create_connection(("127.0.0.1", port))
    s.settimeout(60.0)
    try:
        s.sendall((f"POST /v1/GetRateLimits HTTP/1.1\r\nHost: t\r\nContent-Type: "
                   f"{wire.COLUMNS_CONTENT_TYPE}\r\nContent-Length: {len(frame)}\r\n\r\n"
                   ).encode() + frame)
        buf = b""
        while b"\r\n\r\n" not in buf:
            buf += s.recv(65536)
        head, _, rest = buf.partition(b"\r\n\r\n")
        length = next(int(line.split(b":")[1]) for line in head.split(b"\r\n")
                      if line.lower().startswith(b"content-length"))
        while len(rest) < length:
            rest += s.recv(65536)
        return int(head.split(b" ", 2)[1]), rest[:length]
    finally:
        s.close()


def _columns(case: str, f: int, n: int, word: int):
    """The columns of frame `f` of a case: fresh keys, two of them twice; `word`
    on every lane where it is the calendar bit, on lane 1 alone otherwise."""
    keys = [f"{case}-{f}-{i % (n - 2) if n > 3 else i}" for i in range(n)]
    behavior = np.zeros(n, np.int32)
    duration = np.full(n, 3_600_000, np.int64)
    if word == CALENDAR:
        behavior[:] = CALENDAR
        duration[:] = GREG_HOURS
    elif word:
        behavior[1] = word
    return ([case] * n, keys, np.arange(n, dtype=np.int32) % 2, behavior,
            np.arange(1, n + 1, dtype=np.int64), np.full(n, 1_000, np.int64), duration)


def _oracle_rows(cache, columns) -> np.ndarray:
    names, keys, algo, behavior, hits, limit, duration = columns
    rows = []
    for i in range(len(keys)):
        r = orc.apply(cache, RateLimitRequest(
            name=names[i], unique_key=keys[i], hits=int(hits[i]), limit=int(limit[i]),
            duration=int(duration[i]), algorithm=Algorithm(int(algo[i])),
            behavior=int(behavior[i])), T0)
        rows.append((int(r.status), r.limit, r.remaining, r.reset_time))
    return np.asarray(rows, np.int64)


class _Recorder:
    """`(what, the take's lanes)` in the order things ran, every thread's."""

    def __init__(self):
        self.events: list = []
        self.taken: dict = {}  # a take's lanes -> (n_frames, beh_or)
        self._lock = threading.Lock()

    def note(self, what: str, lanes: int) -> None:
        with self._lock:
            self.events.append((what, lanes))

    def seen(self) -> list:
        with self._lock:
            return list(self.events)


def _until(read, want, what: str) -> None:
    deadline = time.monotonic() + 30.0
    while read() != want:
        assert time.monotonic() < deadline, (what, read(), want)
        time.sleep(0.002)


def _wire_up(monkeypatch, daemon, rec: _Recorder, outcome: str, fail_lanes: int) -> None:
    """Record the launch and every observer; make the take of `fail_lanes`
    lanes fail as `outcome` says."""
    svc, pump = daemon.service, daemon.gateway.pump
    launch = svc.store.apply_columns_async

    def launching(hash_keys, algorithm, *a, **kw):
        lanes = len(algorithm)
        rec.note("launch", lanes)
        if outcome == "launch_raises" and lanes == fail_lanes:
            raise RuntimeError("the launch fell over")
        handle = launch(hash_keys, algorithm, *a, **kw)
        if outcome == "result_raises" and lanes == fail_lanes:
            result = handle.result

            def raising():
                result()  # the store's pipeline still drains in order
                raise RuntimeError("the answer fell over")

            handle.result = raising
        return handle

    monkeypatch.setattr(svc.store, "apply_columns_async", launching)
    # The ring's counters are not a take's: they are booked to the take whose
    # observers called them, which is the one this thread is on.
    on_take = threading.local()
    tap, fold, sketch, stats = (
        svc.blackbox.tap_taken, svc.tenants.fold_admit, svc.hotkeys.update, pump._surface_stats)

    def tapping(tb):
        rec.taken[tb.n] = (tb.n_frames, tb.beh_or)
        rec.note("tap", tb.n)
        return tap(tb)

    def folding(tb):
        rec.note("fold", tb.n)
        return fold(tb)

    def sketching(hashes, keys):
        rec.note("sketch", len(hashes))
        return sketch(hashes, keys)

    observe = pump._observe

    def observing(tb, bt):
        on_take.lanes = tb.n
        try:
            return observe(tb, bt)
        finally:
            on_take.lanes = None

    def counting():
        lanes = getattr(on_take, "lanes", None)
        if lanes is not None:  # an idle pump reads them too
            rec.note("stats", lanes)
        return stats()

    monkeypatch.setattr(svc.blackbox, "tap_taken", tapping)
    monkeypatch.setattr(svc.tenants, "fold_admit", folding)
    monkeypatch.setattr(svc.hotkeys, "update", sketching)
    monkeypatch.setattr(pump, "_observe", observing)
    monkeypatch.setattr(pump, "_surface_stats", counting)


@pytest.mark.parametrize("outcome", ["answers", "launch_raises", "result_raises"])
@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("frames", [1, 3])
def test_observers_run_between_the_launch_and_the_answer(served, monkeypatch, frames, kind, outcome):
    daemon = served
    svc, pump = daemon.service, daemon.gateway.pump
    port = daemon.gateway._edge.port
    word = KINDS[kind]
    case = f"{kind}-{frames}-{outcome}"
    sent = [_columns(case, f, LANES, word) for f in range(frames)]
    primers = [_columns(case + "-primer", f, 2 + f, 0) for f in range(2)] if frames > 1 else []
    take_lanes = frames * LANES
    rec = _Recorder()
    _wire_up(monkeypatch, daemon, rec, outcome, take_lanes)

    ring = svc.blackbox.rings["public"]
    before = {
        "ledger": svc.tenants.totals(), "folds": svc.tenants.batches,
        "sketches": svc.hotkeys.batches, "tapped": ring.stats()[2],
        "audit": audit_mod.ledger_snapshot(), "phases": _get(daemon, "/debug/latency")["phases"],
        "plain": _get(daemon, "/debug/status")["ingress"]["plainTakes"],
        "takes": _get(daemon, "/debug/device")["mesh"]["takes"],
        "queued": svc.multi_region_mgr.queued_hits, "batches": pump.stats()["batches"],
    }

    answers: dict = {}
    seen_at_answer: dict = {}
    errors: list = []

    def client(tag, columns):
        try:
            answers[tag] = _post(port, wire.encode_ingress_frame(columns))
            seen_at_answer[tag] = rec.seen()
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(("primer", f), c))
               for f, c in enumerate(primers)]
    threads += [threading.Thread(target=client, args=(("frame", f), c)) for f, c in enumerate(sent)]
    if frames > 1:
        # Both pump threads on a take of one primer each, held at the depth
        # semaphore; then the frames queue and one take holds them all.
        for _ in range(NativeIngressPump.DEPTH):
            pump._sem.acquire()
    try:
        for i, t in enumerate(threads):
            t.start()
            if i < len(primers):
                _until(lambda: pump.stats()["batches"], before["batches"] + i + 1, "primer taken")
        if frames > 1:
            _until(lambda: pump.stats()["pendingFrames"], frames, "frames queued")
    finally:
        if frames > 1:
            for _ in range(NativeIngressPump.DEPTH):
                pump._sem.release()
    for t in threads:
        t.join(60.0)
    assert not errors, errors
    takes = len(primers) + 1

    # The order.  Nothing observes a take before its launch (on the failing
    # paths too: the launch was tried), and a client that has its answer finds
    # its take tapped, folded and sketched.
    takes_lanes = [take_lanes] + [len(c[1]) for c in primers]
    events = rec.seen()
    for lanes in takes_lanes:
        mine = [what for what, n in events if n == lanes]
        assert mine[0] == "launch" and sorted(mine[1:]) == sorted(OBSERVERS), (lanes, mine)
    for (tag, f), seen in seen_at_answer.items():
        lanes = take_lanes if tag == "frame" else len(primers[f][1])
        assert {what for what, n in seen if n == lanes} == {"launch", *OBSERVERS}, (tag, f, seen)

    # The answers: the oracle's for the frames in the take's order (one take:
    # its frames one after another at one clock reading), or the error's.
    cache = orc.OracleCache()
    for f, columns in enumerate(sent):
        status, body = answers["frame", f]
        if outcome == "answers":
            assert status == 200
            got = wire.decode_ingress_result_frame(body)
            got = np.stack([got.status, got.limit, got.remaining, got.reset_time], axis=1)
            assert (got == _oracle_rows(cache, columns)).all(), (f, got)
        else:
            assert status == 500 and json.loads(body)["code"] == 13
            assert "fell over" in json.loads(body)["message"]
    for f in range(len(primers)):
        assert answers["primer", f][0] == 200

    # What the take held.
    assert rec.taken[take_lanes] == (frames, word)
    for c in primers:
        assert rec.taken[len(c[1])] == (1, 0)
    status = _get(daemon, "/debug/status")
    assert status["ingress"]["plainTakes"] - before["plain"] == takes - (1 if word else 0)
    assert _get(daemon, "/debug/device")["mesh"]["takes"] - before["takes"] == takes

    # Every take observed exactly once, whatever became of it.
    everything = sent + primers
    hits = sum(int(c[4].sum()) for c in everything)
    lanes = sum(len(c[1]) for c in everything)
    ledger = svc.tenants.totals()
    assert ledger["hits"] - before["ledger"]["hits"] == hits
    assert ledger["lanes"] - before["ledger"]["lanes"] == lanes
    assert svc.tenants.batches - before["folds"] == takes
    assert svc.hotkeys.batches - before["sketches"] == takes
    assert ring.stats()[2] - before["tapped"] == len(everything)
    tapped = [r[5] for r in ring.freeze()[-len(everything):]]
    assert sorted(tapped) == sorted(wire.encode_ingress_frame(c) for c in everything)
    audit = audit_mod.ledger_snapshot()
    assert audit.get("ingress_hits", 0) - before["audit"].get("ingress_hits", 0) == hits
    launched_hits = hits - (sum(int(c[4].sum()) for c in sent) if outcome == "launch_raises" else 0)
    assert audit.get("dispatched_hits", 0) - before["audit"].get("dispatched_hits", 0) == launched_hits
    assert _get(daemon, "/debug/audit")["violationTotal"] == 0
    queued = sum(int(c[4][1]) for c in sent) if word == MULTI_REGION else 0
    assert svc.multi_region_mgr.queued_hits - before["queued"] == queued

    # The per-dispatch metrics' divisors: one of each a take.
    phases = _get(daemon, "/debug/latency")["phases"]
    for name in ("pump.admit", "calendar.resolve", "behavior.handle"):
        grown = phases[name]["count"] - before["phases"].get(name, {"count": 0})["count"]
        assert grown == takes, (name, grown)
