"""Incident black box (gubernator_tpu/blackbox.py) + replay.

Units for the ring byte budget, the tap classifier, trigger
coalescing / rate limiting / manual bypass, and bounded retention;
loader fuzz (truncation, bit flips, wrong versions, manifest damage
— every defect must reject the WHOLE bundle, never half-replay) with
scripts/blackbox_fsck.py exit codes; the GUBER_BLACKBOX=0 wire-byte
identity golden; and the acceptance oracle: a seeded FaultPlan
DUPLICATE on a live 2-daemon cluster trips forward_conservation,
auto-writes a bundle, and scripts/replay.py reproduces the same
violation from the bundle — deterministically, twice.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time

import pytest

from gubernator_tpu import audit, blackbox, faults, tracing, wire
from gubernator_tpu.cluster import Cluster
from gubernator_tpu.config import BehaviorConfig
from gubernator_tpu.types import GetRateLimitsRequest, RateLimitRequest
from gubernator_tpu.utils.clock import Clock

SCRIPTS = os.path.join(os.path.dirname(__file__), "..", "scripts")


def _script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean():
    tracing.reset()
    blackbox.force_disable(False)
    blackbox.set_enabled(True)
    yield
    tracing.reset()
    faults.uninstall()
    blackbox.force_disable(False)


def _cols(key: str = "k", hits: int = 3):
    return (["bb"], [key], [1], [0], [hits], [1000], [60_000])


def _peer_frame(key: str = "k", hits: int = 3) -> bytes:
    return wire.encode_columns_frame(_cols(key, hits))


# ---------------------------------------------------------------------
# Rings + taps
# ---------------------------------------------------------------------
def test_ring_byte_budget_evicts_oldest():
    ring = blackbox._WireRing(budget=4096)
    frames = [_peer_frame(f"key-{i:04d}") for i in range(200)]
    for i, f in enumerate(frames):
        ring.record((i, i, "in", "", 1, f))
    n, nbytes, total = ring.stats()
    assert total == 200          # lifetime count survives eviction
    assert n < 200               # budget forced evictions
    assert nbytes <= 4096
    kept = ring.freeze()
    # Evict-oldest: what remains is exactly the newest suffix, in order.
    assert [r[5] for r in kept] == frames[200 - n:]


def test_tap_classifies_by_kind_and_sniffs_magic(tmp_path):
    bb = blackbox.BlackBox(None, path=str(tmp_path), budget_mb=1)
    bb.tap("in", "", b'{"requests": []}')       # JSON body: ignored
    bb.tap("in", "", b"GU")                     # short junk: ignored
    bb.tap("in", "", wire.encode_ingress_frame(_cols()))
    bb.tap("out", "10.0.0.2:81", _peer_frame())
    bb.tap("out", "10.0.0.2:81",
           wire.encode_columns_frame(_cols(), kind=3))
    expect = {"public": 1, "peer": 1, "global": 1,
              "transfer": 0, "region": 0}
    got = {w: bb.rings[w].stats()[0] for w in blackbox.WIRES}
    assert got == expect
    rec = bb.rings["peer"].freeze()[0]
    assert (rec[2], rec[3], rec[4]) == ("out", "10.0.0.2:81", 1)


def test_force_disable_is_dark(tmp_path):
    bb = blackbox.BlackBox(None, path=str(tmp_path), budget_mb=1)
    blackbox.force_disable(True)
    assert not bb.live()
    bb.tap("in", "", wire.encode_ingress_frame(_cols()))
    bb.on_trigger("audit-violation", {})
    blackbox.force_disable(False)
    assert all(bb.rings[w].stats() == (0, 0, 0) for w in blackbox.WIRES)
    assert bb._pending == []


# ---------------------------------------------------------------------
# Triggers: coalescing, rate limit, manual bypass, retention
# ---------------------------------------------------------------------
def _wait_bundles(path: str, n: int = 1, timeout_s: float = 10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        found = [os.path.join(path, e) for e in blackbox.list_bundles(path)]
        if len(found) >= n:
            return found
        time.sleep(0.02)
    raise AssertionError(
        f"no {n} bundles under {path} within {timeout_s}s: "
        f"{blackbox.list_bundles(path)}"
    )


def test_trigger_storm_coalesces_into_one_bundle(tmp_path):
    bb = blackbox.BlackBox(None, path=str(tmp_path), budget_mb=1)
    bb.coalesce_s = 0.05
    try:
        for i in range(5):
            bb.on_trigger("breaker-open", {"peer": f"p{i}"})
        bundles = _wait_bundles(str(tmp_path), 1)
        assert len(bundles) == 1
        manifest = json.loads(
            (tmp_path / os.path.basename(bundles[0]) / "manifest.json")
            .read_bytes()
        )
        assert len(manifest["triggers"]) == 5
        assert {t["kind"] for t in manifest["triggers"]} == {"breaker-open"}
    finally:
        bb.close()


def test_rate_limit_suppresses_and_manual_bypasses(tmp_path):
    bb = blackbox.BlackBox(None, path=str(tmp_path), budget_mb=1)
    bb.coalesce_s = 0.02
    bb.min_interval_s = 3600.0
    try:
        bb.on_trigger("audit-violation", {"invariant": "x"})
        _wait_bundles(str(tmp_path), 1)
        # Inside the rate-limit window: triggers are counted, not
        # written.
        bb.on_trigger("audit-violation", {"invariant": "x"})
        deadline = time.monotonic() + 5.0
        while (bb.snapshot()["suppressedTriggers"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert len(blackbox.list_bundles(str(tmp_path))) == 1
        assert bb.snapshot()["suppressedTriggers"] == 1
        # The operator bypass: a manual trigger writes despite the
        # window and carries the suppressed count into the manifest.
        bb.trigger_manual("on purpose")
        bundles = _wait_bundles(str(tmp_path), 2)
        manifest = json.loads(
            (tmp_path / os.path.basename(bundles[-1]) / "manifest.json")
            .read_bytes()
        )
        assert manifest["suppressedTriggers"] >= 1
        assert manifest["triggers"][-1]["kind"] == "manual"
    finally:
        bb.close()


def test_retention_prunes_oldest(tmp_path):
    bb = blackbox.BlackBox(None, path=str(tmp_path), budget_mb=1, retain=2)
    try:
        names = [
            os.path.basename(bb.write_bundle([{"kind": "manual"}]))
            for _ in range(4)
        ]
        kept = [os.path.basename(p)
                for p in blackbox.list_bundles(str(tmp_path))]
        assert kept == names[-2:]
    finally:
        bb.close()


# ---------------------------------------------------------------------
# Loader fuzz: any defect rejects the whole bundle (and fsck agrees)
# ---------------------------------------------------------------------
def _good_bundle(tmp_path) -> str:
    bb = blackbox.BlackBox(None, path=str(tmp_path), budget_mb=1)
    bb.tap("in", "", wire.encode_ingress_frame(_cols("a")))
    bb.tap("out", "p:1", _peer_frame("b"))
    bb.tap("out", "p:1", _peer_frame("c", hits=5))
    path = bb.write_bundle([{"kind": "manual", "wallNs": 1, "monoNs": 1,
                             "fields": {}}])
    bb.close()
    return path


def _flip_byte(path: str, offset: int) -> None:
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))


CORRUPTIONS = [
    ("gfl-truncated", lambda d: open(
        os.path.join(d, "wire-peer.gfl"), "r+b").truncate(
        os.path.getsize(os.path.join(d, "wire-peer.gfl")) - 3)),
    ("gfl-bit-flip", lambda d: _flip_byte(
        os.path.join(d, "wire-peer.gfl"),
        os.path.getsize(os.path.join(d, "wire-peer.gfl")) - 5)),
    ("gfl-bad-magic", lambda d: _flip_byte(
        os.path.join(d, "wire-public.gfl"), 0)),
    ("file-missing", lambda d: os.unlink(
        os.path.join(d, "wire-global.gfl"))),
    ("manifest-garbage", lambda d: open(
        os.path.join(d, "manifest.json"), "wb").write(b"not json")),
    ("manifest-wrong-version", lambda d: _rewrite_manifest(
        d, lambda m: m.__setitem__("version", 999))),
    ("manifest-wrong-format", lambda d: _rewrite_manifest(
        d, lambda m: m.__setitem__("format", "something-else"))),
    ("manifest-bad-crc", lambda d: _rewrite_manifest(
        d, lambda m: m["files"]["wire-peer.gfl"].__setitem__("crc32", 1))),
]


def _rewrite_manifest(bundle_dir: str, mutate) -> None:
    p = os.path.join(bundle_dir, "manifest.json")
    with open(p) as f:
        m = json.load(f)
    mutate(m)
    with open(p, "w") as f:
        json.dump(m, f)


@pytest.mark.parametrize("name,corrupt", CORRUPTIONS,
                         ids=[c[0] for c in CORRUPTIONS])
def test_corrupt_bundle_never_half_loads(tmp_path, name, corrupt):
    bundle = _good_bundle(tmp_path)
    assert blackbox.load_bundle(bundle).merged_records()
    corrupt(bundle)
    with pytest.raises(blackbox.BundleError):
        blackbox.load_bundle(bundle)
    # replay refuses before driving a single frame...
    replay = _script("replay")
    with pytest.raises(blackbox.BundleError):
        replay.replay_bundle(bundle)
    # ...and the offline verifier exits 1 on exactly the same defect.
    assert _script("blackbox_fsck").main([bundle]) == 1


def test_fsck_ok_and_usage_exits(tmp_path, capsys):
    bundle = _good_bundle(tmp_path)
    fsck = _script("blackbox_fsck")
    assert fsck.main([bundle, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ok"] is True
    assert doc["frames"]["peer"] == 2 and doc["frames"]["public"] == 1
    assert fsck.main([str(tmp_path / "nope")]) == 2


def test_incident_collect_stitches_and_rejects(tmp_path, capsys):
    a = _good_bundle(tmp_path / "a")
    b = _good_bundle(tmp_path / "b")
    ic = _script("incident_collect")
    assert ic.main(["--scan", str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert len(doc["bundles"]) == 2 and not doc["rejected"]
    assert len(doc["frames"]) == 6  # 3 per bundle, one merged timeline
    assert [t["kind"] for t in doc["triggers"]] == ["manual", "manual"]
    _flip_byte(os.path.join(b, "wire-peer.gfl"), 20)
    assert ic.main([a, b]) == 1
    capsys.readouterr()


def test_cluster_status_blackbox_column():
    cs = _script("cluster_status")
    assert cs.COLUMNS[-1] == "blackbox"
    row = cs.summarize("a:1", {"blackbox": {
        "enabled": True, "bundles": 2, "bundlesOnDisk": 3,
        "lastTriggerAgeS": 31.4,
    }})
    assert row["blackbox"] == "2/3 31s ago"
    assert cs.summarize("a:1", {})["blackbox"] == "-"


# ---------------------------------------------------------------------
# GUBER_BLACKBOX=0 golden: the wire is byte-identical either way
# ---------------------------------------------------------------------
def _mini_service(blackbox_dir: str = ""):
    from gubernator_tpu.service import ServiceConfig, V1Service

    clock = Clock()
    clock.freeze(1_573_430_400_000)
    behaviors = BehaviorConfig(audit=False, snapshot_interval_s=0.0)
    svc = V1Service(ServiceConfig(
        cache_size=1024,
        behaviors=behaviors,
        advertise_address="bbtest:0",
        clock=clock,
        blackbox_dir=blackbox_dir,
    ))
    svc.set_peers([])
    return svc


def test_disabled_wire_bytes_identical_and_rings_dark():
    from gubernator_tpu import gateway

    frames = [wire.encode_ingress_frame(_cols(f"gk{i}", hits=2))
              for i in range(4)]

    def drive(svc):
        out = []
        for f in frames:
            status, _ct, body = gateway.handle_request(
                svc, "POST", "/v1/GetRateLimits", f
            )
            assert status == 200
            out.append(bytes(body))
        return out

    svc_on = _mini_service()
    try:
        on_bodies = drive(svc_on)
        assert svc_on.blackbox.rings["public"].stats()[0] == 8  # req+resp
    finally:
        svc_on.close()
    blackbox.force_disable(True)
    svc_off = _mini_service()
    try:
        off_bodies = drive(svc_off)
        assert all(
            svc_off.blackbox.rings[w].stats() == (0, 0, 0)
            for w in blackbox.WIRES
        )
    finally:
        svc_off.close()
        blackbox.force_disable(False)
    assert on_bodies == off_bodies


# ---------------------------------------------------------------------
# /debug/incident + debug surfaces
# ---------------------------------------------------------------------
def test_debug_incident_endpoint_and_surfaces(tmp_path):
    from gubernator_tpu import gateway

    svc = _mini_service(blackbox_dir=str(tmp_path))
    try:
        svc.blackbox.coalesce_s = 0.02
        status, _ct, body = gateway.handle_request(
            svc, "POST", "/debug/incident", b'{"reason": "drill"}'
        )
        assert status == 202, body
        bundles = _wait_bundles(str(tmp_path), 1)
        manifest = json.loads(
            open(os.path.join(bundles[0], "manifest.json"), "rb").read()
        )
        assert manifest["triggers"][0]["kind"] == "manual"
        assert manifest["service"]["advertiseAddress"] == "bbtest:0"
        # debug_status carries the blackbox section cluster_status reads.
        # The bundle is on disk (renamed, its directory fsynced) before the
        # writer's thread counts it: give the count the moment it needs.
        deadline = time.monotonic() + 10.0
        snap = svc.debug_status()["blackbox"]
        while snap["bundles"] < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
            snap = svc.debug_status()["blackbox"]
        assert snap["enabled"] and snap["bundles"] >= 1
        assert snap["ringBudgetBytes"] > 0
        # /metrics: the gubernator_blackbox_* families render.
        status, _ct, metrics_body = gateway.handle_request(
            svc, "GET", "/metrics", b""
        )
        text = metrics_body.decode()
        for family in (
            "gubernator_blackbox_frames_total",
            "gubernator_blackbox_ring_bytes",
            "gubernator_blackbox_bundles_total",
            "gubernator_blackbox_last_trigger_age_seconds",
        ):
            assert family in text, family
        # Disabled process-wide: the endpoint refuses (403).
        blackbox.force_disable(True)
        status, _ct, body = gateway.handle_request(
            svc, "POST", "/debug/incident", b""
        )
        assert status == 403
        blackbox.force_disable(False)
    finally:
        svc.close()
    # No bundle dir configured: 409, bundles cannot be written.
    svc2 = _mini_service()
    try:
        status, _ct, body = gateway.handle_request(
            svc2, "POST", "/debug/incident", b""
        )
        assert status == 409
    finally:
        svc2.close()


# ---------------------------------------------------------------------
# The acceptance oracle: capture -> bundle -> deterministic replay
# ---------------------------------------------------------------------
@pytest.mark.chaos
@pytest.mark.slow  # live 2-daemon cluster + two full replays: `make chaos` runs it
def test_seeded_incident_bundle_replays_deterministically(tmp_path):
    """FaultPlan DUPLICATE double-delivers the forward wire on a live
    2-daemon cluster; the audit trips forward_conservation, whose
    auto-dump freezes the rings into a bundle.  scripts/replay.py then
    re-drives the captured frames against a fresh daemon and must
    reproduce the SAME violation — twice, with byte-identical
    reports."""
    cl = Cluster().start(2)
    plan = faults.FaultPlan(seed=11)
    plan.duplicate(op="GetPeerRateLimits")
    try:
        for i, d in enumerate(cl.daemons):
            d.service.blackbox.path = str(tmp_path / f"d{i}")
            d.service.blackbox.coalesce_s = 0.05
        svc0 = cl.daemons[0].service
        auditor = svc0.auditor
        auditor.arm()
        auditor.check_now()  # seed pass (see Auditor.arm)
        faults.install(plan)
        me = svc0.advertise_address
        import hashlib

        cand = [hashlib.md5(str(i).encode()).hexdigest() for i in range(64)]
        reqs = [
            RateLimitRequest(
                name="bb", unique_key=uk, hits=3, limit=1000,
                duration=60_000,
            )
            for uk in cand
            if svc0.get_peer(
                RateLimitRequest(name="bb", unique_key=uk).hash_key()
            ).info.grpc_address != me
        ]
        assert reqs, "no remotely-owned keys in the probe range"
        svc0.get_rate_limits(GetRateLimitsRequest(requests=reqs))
        found = auditor.check_now()
        assert "forward_conservation" in [v["invariant"] for v in found]
        faults.uninstall()
        # The violation's auto-dump must have frozen a bundle.
        bundles = _wait_bundles(str(tmp_path / "d0"), 1)
        bundle = bundles[-1]
        assert _script("blackbox_fsck").main([bundle]) == 0
        manifest = json.loads(
            open(os.path.join(bundle, "manifest.json"), "rb").read()
        )
        assert "audit-violation" in [
            t["kind"] for t in manifest["triggers"]
        ]
        # The duplicated delivery is IN the capture: at least one
        # byte-identical consecutive outbound pair on the peer wire.
        peer_out = [
            r[5] for r in blackbox.load_bundle(bundle).frames["peer"]
            if r[2] == "out"
        ]
        assert any(
            a == b for a, b in zip(peer_out, peer_out[1:])
        ), "no duplicated forward frame captured"
    finally:
        faults.uninstall()
        cl.stop()

    replay = _script("replay")
    first = replay.replay_bundle(bundle)
    second = replay.replay_bundle(bundle)
    assert json.dumps(first, sort_keys=True) == json.dumps(
        second, sort_keys=True
    )
    assert first["violations"].get("forward_conservation", 0) >= 1
    assert first["bundleViolations"].get("forward_conservation", 0) >= 1
    assert first["reproducesBundleViolations"] is True
    # --to-test: the emitted regression file is a valid pytest module
    # pinned to this bundle.
    out = tmp_path / "test_incident_regression.py"
    replay.emit_test(bundle, str(out))
    src = out.read_text()
    compile(src, str(out), "exec")
    assert "def test_" in src and os.path.basename(bundle) in src


# ---------------------------------------------------------------------
# The native ingress lane's tap: the bytes the client sent
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def native_daemon():
    import jax

    from gubernator_tpu import native
    from gubernator_tpu.cluster import fast_test_behaviors
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import Daemon

    if not native.available():
        pytest.skip("the native ingress lane needs the host runtime")
    clock = Clock()
    clock.freeze(1_790_000_000_000)
    daemon = Daemon(DaemonConfig(
        listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0",
        cache_size=4096, behaviors=fast_test_behaviors(),
        peer_discovery_type="static", native_http=True,
        devices=jax.devices()[:1], warmup_shapes=[],
    ), clock=clock).start()
    daemon.set_peers([daemon.peer_info])
    try:
        yield daemon
    finally:
        daemon.close()


def _ingress_frame(tag: str, n: int, calendar_lane: bool = False) -> bytes:
    import numpy as np

    from gubernator_tpu.types import Behavior

    behavior = np.zeros(n, np.int32)
    duration = np.full(n, 60_000, np.int64)
    if calendar_lane:  # an hour of the calendar on lane 1
        behavior[1] = int(Behavior.DURATION_IS_GREGORIAN)
        duration[1] = 1
    return wire.encode_ingress_frame((
        ["bbn"] * n, [f"{tag}-{i}" for i in range(n)], np.zeros(n, np.int32),
        behavior, np.arange(1, n + 1, dtype=np.int64),
        np.full(n, 1_000, np.int64), duration,
    ))


@pytest.mark.parametrize("shape", ["one-frame", "three-frames-a-take",
                                   "calendar-lane"])
def test_native_take_records_the_sent_bytes_and_a_bundle_replays_them(
        native_daemon, tmp_path, shape):
    """What the black box holds of a native take is what the clients
    sent, byte for byte (the tap copies `IngressFrame::body`; it used to
    re-encode the take's columns), a record a frame, also of a take that
    coalesced three frames; and a bundle of them replays: every frame
    drives the public endpoint of a fresh service and is answered 200."""
    import threading
    import urllib.request

    from gubernator_tpu.gateway import NativeIngressPump

    daemon = native_daemon
    svc, pump = daemon.service, daemon.gateway.pump
    port = daemon.gateway._edge.port
    svc.blackbox.path = str(tmp_path)
    svc.blackbox.coalesce_s = 0.02
    ring = svc.blackbox.rings["public"]
    tapped, takes = ring.stats()[2], pump.stats()["batches"]

    def post(frame: bytes) -> None:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/GetRateLimits", data=frame,
            headers={"Content-Type": wire.COLUMNS_CONTENT_TYPE},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
            assert wire.decode_ingress_result_frame(r.read()).n > 0

    def until(read, want):
        deadline = time.monotonic() + 30.0
        while read() != want:
            assert time.monotonic() < deadline, (read(), want)
            time.sleep(0.002)

    if shape == "three-frames-a-take":
        # Both pump threads on a take of one frame each, held at the depth
        # semaphore; the next three queue and ride ONE take.
        frames = [_ingress_frame(f"{shape}-{f}", 2 + f) for f in range(5)]
        threads = [threading.Thread(target=post, args=(f,)) for f in frames]
        for _ in range(NativeIngressPump.DEPTH):
            pump._sem.acquire()
        try:
            for i, t in enumerate(threads):
                t.start()
                if i < 2:
                    until(lambda: pump.stats()["batches"], takes + i + 1)
            until(lambda: pump.stats()["pendingFrames"], 3)
        finally:
            for _ in range(NativeIngressPump.DEPTH):
                pump._sem.release()
        for t in threads:
            t.join(60.0)
        assert pump.stats()["batches"] - takes == 3
    else:
        frames = [_ingress_frame(shape, 9, shape == "calendar-lane")]
        post(frames[0])
    assert ring.stats()[2] - tapped == len(frames)
    records = ring.freeze()[-len(frames):]
    assert sorted(r[5] for r in records) == sorted(frames)
    assert {(r[2], r[4]) for r in records} == {("in", 5)}

    svc.blackbox.trigger_manual("drill")
    bundle = _wait_bundles(str(tmp_path), 1)[-1]
    assert _script("blackbox_fsck").main([bundle]) == 0
    captured = [r[5] for r in blackbox.load_bundle(bundle).frames["public"]]
    assert sorted(captured[-len(frames):]) == sorted(frames)
    report = _script("replay").replay_bundle(bundle)
    assert report["driven"]["public"] == len(captured)
    assert report["responseStatuses"] == {"200": len(captured)}
